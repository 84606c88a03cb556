"""The program's model as the harness sees it.

``program_arch`` builds the program's ``ArchConfig`` for a configuration
file: the arch the file names, with every key the file states taken
through the mapping in ``bench/arch_keys/*.json`` (the files' ``about``
says what each section means).  Nothing here names a field or a key: a
configuration with other keys brings a mapping file of its own.  The
run stops, naming each, on a key that the mapping neither takes nor
lists as inert, on a key stated at a value the program does not run,
and on a field of the program's arch that no key sets and that holds
another value than the file's silence means (``unset_must_be``, else
the ``ArchConfig`` default), such as the experts of an arch whose file
states none.

A key that a mapping file maps under ``keys`` goes through that
mapping, even where another file lists it under ``runs``: ``runs``
guards a key only while no file maps it.  So a configuration that
states another norm epsilon than the one the program runs brings a
file that maps the key onto the program's field, and every file that
states the key then sets that field.

``kv_pool`` reads the row shape and dtype of each attention pool tensor
of a gateway the program built.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

__all__ = ["KEYS_DIR", "load_keys", "program_arch", "kv_pool"]

KEYS_DIR = Path(__file__).resolve().parent / "arch_keys"
CONVERSIONS = {"int": int, "float": float, "bool": bool, "str": str}


def load_keys(directory: Path = KEYS_DIR) -> dict:
    """Every mapping file in ``directory``, merged; a key or field that
    two files give different meanings stops the load."""
    merged = {"keys": {}, "runs": {}, "unstated": {}, "unset_must_be": {},
              "inert": set(), "policy": set()}
    for path in sorted(directory.glob("*.json")):
        part = json.loads(path.read_text())
        for section, table in merged.items():
            if isinstance(table, set):
                table.update(part.get(section, ()))
                continue
            for key, value in part.get(section, {}).items():
                if table.setdefault(key, value) != value:
                    raise ValueError(f"{path.name}: {section} {key!r} is "
                                     f"{value!r} here, {table[key]!r} in "
                                     f"another mapping file")
    return merged


def _convert(conv, value):
    if isinstance(conv, str):
        return CONVERSIONS[conv](value)
    if "table" in conv:
        return conv["table"][value]
    return conv["value"]


def _stated(cfg: dict, keys: dict):
    """(key, value) of every key the file states and not as null; the
    keys of a group that is neither mapped nor inert as ``group.key``."""
    for key, value in cfg.items():
        if value is None or key in keys["inert"]:
            continue
        if isinstance(value, dict) and key not in keys["keys"]:
            yield from _stated({f"{key}.{k}": v for k, v in value.items()},
                               keys)
        else:
            yield key, value


def _default(field: dataclasses.Field):
    if field.default is not dataclasses.MISSING:
        return field.default
    if field.default_factory is not dataclasses.MISSING:
        return field.default_factory()
    return dataclasses.MISSING


def program_arch(cfg: dict, keys: dict | None = None):
    """The program's ArchConfig for a configuration file (module
    docstring); ``keys`` is the merged mapping, ``load_keys()`` unless
    given."""
    import repro.configs as configs

    keys = load_keys() if keys is None else keys
    base = configs.get_config(cfg["arch"])
    fields, nested, refused = {}, {}, []
    for key, value in _stated(cfg, keys):
        if key not in keys["keys"]:
            if key not in keys["runs"]:
                refused.append(f"{key} (not mapped)")
            elif value != keys["runs"][key]:
                refused.append(f"{key}={value!r} (the program runs "
                               f"{keys['runs'][key]!r})")
            continue
        for field, conv in keys["keys"][key].items():
            outer, _, inner = field.partition(".")
            if inner:
                nested.setdefault(outer, {})[inner] = _convert(conv, value)
            else:
                fields[field] = _convert(conv, value)
    for outer, inner in nested.items():
        fields[outer] = dataclasses.replace(getattr(base, outer), **inner)
    for field in dataclasses.fields(base):
        name = field.name
        if name in fields or name in keys["policy"]:
            continue
        if name in keys["unstated"]:
            fields[name] = keys["unstated"][name]
            continue
        want = keys["unset_must_be"].get(name, _default(field))
        if getattr(base, name) != want:
            refused.append(f"{name}={getattr(base, name)!r} in the "
                           f"program's arch, which no stated key sets "
                           f"(it must be {want!r})")
    if refused:
        raise ValueError(f"{cfg['arch']}: the program's mapping of published "
                         f"keys does not take " + "; ".join(refused))
    return dataclasses.replace(base, **fields)


def kv_pool(gw) -> dict:
    """``{position: {tensor: (row shape, dtype)}}`` of every attention
    pool the gateway built; a row is one cached position of one layer.
    Read from the engine's pools, ``(layers x (n_pages + 1), page_size,
    *row)`` each: ``ServingGateway`` has no public accessor for them
    yet, so this is the one place the harness reads its private
    ``_pools``."""
    return {name: {kk: (tuple(a.shape[2:]), a.dtype)
                   for kk, a in pools.items()}
            for name, pools in gw._pools.items()}
