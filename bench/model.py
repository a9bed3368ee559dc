"""The program's configuration for a configuration file, its reference
family, and the peaks.

A configuration file names a configuration the program registers and
the overrides that bring it to the published sizes (``program``), and
the reference family that computes and counts the same model
(``reference``: ``bench/references/<name>.py``).  The family checks
the program's configuration key by key against the file's published
keys, which its reference reads, so the program and the reference
cannot be handed two different models.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Dict

ROOT = pathlib.Path(__file__).resolve().parents[1]
PEAKS = ROOT / "bench" / "peaks.json"


def load_file(path: pathlib.Path, name: str):
    """The module in the file ``path``, loaded by path as ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the class is made
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def family(cfgfile: Dict, root: pathlib.Path = ROOT):
    """The reference family the configuration file names, loaded by
    path from ``bench/references/`` under ``root``."""
    name = cfgfile["reference"]
    path = root / "bench" / "references" / f"{name}.py"
    if "/" in name or name.startswith(".") or not path.is_file():
        raise FileNotFoundError(f"no reference family {name!r}: looked "
                                f"for {path}")
    return load_file(path, f"bench_reference_{name}")


def program_config(cfgfile: Dict, ref):
    """The registered configuration with the file's overrides, checked
    by the reference family ``ref`` against the published keys."""
    from repro.configs.base import get_config
    prog = cfgfile["program"]
    cfg = get_config(prog["registered"]).with_overrides(
        **prog.get("overrides", {}))
    ref.check_program(cfgfile, cfg)
    if not cfg.use_pallas_kernels:
        raise ValueError("the served path runs the Pallas kernels")
    return cfg


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    p = table[device_kind]
    return {"flops": float(p["flops_bf16"]),
            "bytes_per_s": float(p["hbm_bytes_per_s"])}


__all__ = ["PEAKS", "family", "load_file", "peaks", "program_config"]
