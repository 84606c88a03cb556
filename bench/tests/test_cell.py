"""A cell defined only by new files under bench/ is found by name."""

import json
import shutil

from bench import cell as cellmod

NEW_METRIC = '''
UNIT = "%"


def read(ctx):
    return 42.0
'''


def test_new_cell_needs_only_new_files(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(cellmod.BENCH / "configs", bench / "configs")
    shutil.copytree(cellmod.BENCH / "traffic", bench / "traffic")
    (bench / "metrics").mkdir()
    spec = json.loads((cellmod.BENCH.parent / "BENCHMARK.json").read_text())
    # the additions a later PR would make: a config, a mix, a metric,
    # and their entries in BENCHMARK.json
    cfg = json.loads((bench / "configs" / "olmo-1b.json").read_text())
    (bench / "configs" / "olmo-1b-copy.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "lmsys-chat.json").read_text())
    mix["slots"] = 8
    (bench / "traffic" / "short-chat.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new_metric.x.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "olmo-1b-copy", "source": "x",
                            "file": "bench/configs/olmo-1b-copy.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "olmo-1b-copy.short-chat",
                              "config": "olmo-1b-copy",
                              "traffic": "short-chat", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "new_metric.x", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "x", "moves": "tok_s",
                              "workloads": ["olmo-1b-copy.short-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    found = cellmod.find_cell("olmo-1b-copy.short-chat", bench)
    assert found.config == cfg and found.mix["slots"] == 8
    assert found.chips == 1
    names = [m["name"] for m in found.per_layer]
    assert "new_metric.x" in names and "prefill_attn_roofline" not in names
    assert cellmod.load_metric("new_metric.x", bench).read(None) == 42.0
    assert cellmod.load_entry(found.mix["entry"]).run is not None
    assert cellmod.load_reference(found.config["reference"]).token_gaps


def test_every_benchmark_name_resolves_to_files():
    spec = json.loads((cellmod.BENCH.parent / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = cellmod.find_cell(w["name"])
        assert c.end_to_end and c.per_layer
        for m in c.per_layer:
            assert callable(cellmod.load_metric(m["name"]).read)
    for c in spec["configs"]:
        assert (cellmod.BENCH.parent / c["file"]).exists()
