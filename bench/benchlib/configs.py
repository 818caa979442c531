"""A configuration's data files: located, checked against their sha256."""
from __future__ import annotations

import hashlib
import os

import numpy as np


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def data_path(cfg: dict, key: str) -> str:
    """Path (without extension for multi-file data) of ``cfg["data"][key]``,
    after every file of it has matched the sha256 the configuration
    records."""
    base = os.path.join(cfg["_dir"], cfg["data"][key])
    files = [f for f in cfg["sha256"] if f == cfg["data"][key]
             or os.path.splitext(f)[0] == cfg["data"][key]]
    if not files:
        raise ValueError(f"configuration {cfg['name']!r} records no sha256 "
                         f"for {cfg['data'][key]!r}")
    for f in files:
        got = _sha256(os.path.join(cfg["_dir"], f))
        if got != cfg["sha256"][f]:
            raise ValueError(f"{f}: sha256 {got} differs from the "
                             f"configuration's {cfg['sha256'][f]}")
    return base


def test_split(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    d = np.load(data_path(cfg, "test"))
    return np.asarray(d["x"], np.float32), np.asarray(d["y"], np.int32)

