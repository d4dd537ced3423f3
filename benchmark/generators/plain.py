"""The plain record of a generated cluster: what the reference reads.

Everything here is numpy and builtins. The generators fill it from the
same numbers they hand to the program's structs, so the reference never
reads the program's store.
"""

from __future__ import annotations

import numpy as np

#: resident usage and capacity, one entry a node
_FLOATS = ("cap_cpu", "cap_mem", "cap_disk", "cap_gpu",
           "used_cpu", "used_mem", "used_disk", "used_gpu")
_STRINGS = ("node_ids", "node_class", "datacenter", "rack")


def new_plain(n_nodes: int) -> dict:
    plain = {k: np.zeros(n_nodes, np.float64) for k in _FLOATS}
    plain.update({k: [""] * n_nodes for k in _STRINGS})
    return plain


def save_plain(path: str, plain: dict) -> None:
    arrays = {k: plain[k] for k in _FLOATS}
    arrays.update({k: np.array(plain[k], dtype=np.str_) for k in _STRINGS})
    np.savez(path, **arrays)


def load_plain(path: str) -> dict:
    with np.load(path, allow_pickle=False) as z:
        plain = {k: z[k].astype(np.float64) for k in _FLOATS}
        plain.update({k: z[k].tolist() for k in _STRINGS})
    return plain


def seeded_uuid(rng) -> str:
    h = rng.bytes(16).hex()
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"
