"""Structure cache: what a query or search cell serves, built once per
checkout and restored by the runs after it.

An entry is keyed by a hash of every file under ``src/`` (the program
that built it), the configuration and what is cached, so a changed
program or configuration builds afresh. Entries live under
``chipbench/.cache/`` (git-ignored, a fixed path inside the checkout).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def key(config: dict, what: str) -> str:
    h = hashlib.sha256()
    for p in sorted((ROOT / "src").rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(ROOT)).encode())
            h.update(p.read_bytes())
    h.update(json.dumps(config, sort_keys=True).encode())
    h.update(what.encode())
    return f"{what}-{config['name']}-{h.hexdigest()[:16]}"


def save_leaves(tree, path: Path) -> None:
    """Write a pytree with its leaves as numpy arrays (atomic rename)."""
    import jax
    import numpy as np
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "wb") as f:
        pickle.dump(jax.tree.map(np.asarray, tree), f,
                    protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)


def load_leaves(path: Path):
    """The pytree ``save_leaves`` wrote, its leaves put on the device.
    Only files this benchmark wrote into its own cache are read."""
    import jax
    with open(path, "rb") as f:
        tree = pickle.load(f)
    return jax.device_put(tree)
