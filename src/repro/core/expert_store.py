"""Host-tier expert parameter store.

Experts live here (host RAM, numpy) by default — the "offloaded" tier.
Weights keep the dtype they are given (bf16 or fp32), or are stored as
int8 per-channel quantization (the TPU-native stand-in for the paper's
2-bit HQQ GPU kernels; see DESIGN.md §hardware-adaptation). ``dtype`` is
the given dtype either way: the device slots the experts stream into
take it. Byte accounting is real (``nbytes`` of what is actually
stored).
"""
from __future__ import annotations

import zlib
from typing import Dict, Optional, Tuple

import numpy as np

Key = Tuple[int, int]  # (layer, expert_id)


def payload_checksum(weights: dict) -> int:
    """crc32 over the fp32 payload bytes, matrices in name order. Fast
    enough to run per delivery under fault injection, strong enough to
    catch any single flipped byte (see ``ExpertStore.verify``)."""
    crc = 0
    for name in sorted(weights):
        arr = np.ascontiguousarray(weights[name], dtype=np.float32)
        crc = zlib.crc32(arr.tobytes(), crc)
    return crc


def _quantize_int8(w: np.ndarray):
    scale = np.max(np.abs(w), axis=0, keepdims=True) / 127.0
    scale = np.where(scale == 0, 1.0, scale)
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


class ExpertStore:
    def __init__(self, *, quant: str = "none"):
        if quant not in ("none", "int8"):
            raise ValueError(f"quant must be 'none' or 'int8', got {quant!r}")
        self.quant = quant
        self.dtype: Optional[np.dtype] = None  # of the weights given to put
        self._data: Dict[Key, dict] = {}
        self._checksums: Dict[Key, int] = {}  # lazy, of the fp32 payload

    def put(self, key: Key, weights: dict) -> None:
        """weights: {'w1': [d,ff], 'w3': [d,ff], 'w2': [ff,d]} (device or
        np), all of one dtype, the same for every expert."""
        host = {k: np.asarray(v) for k, v in weights.items()}
        dtypes = {v.dtype for v in host.values()}
        if self.dtype is not None:
            dtypes.add(self.dtype)
        if len(dtypes) != 1:
            raise ValueError(
                f"expert weights mix dtypes {sorted(map(str, dtypes))}")
        self.dtype = dtypes.pop()
        if self.quant == "int8":
            entry = {}
            for k, v in host.items():
                q, s = _quantize_int8(v.astype(np.float32))
                entry[k] = ("int8", q, s)
            self._data[key] = entry
        else:
            self._data[key] = {k: ("raw", v, None) for k, v in host.items()}
        self._checksums.pop(key, None)

    def fetch(self, key: Key) -> dict:
        """Host weights: as stored, or dequantized to fp32 (int8)."""
        entry = self._data[key]
        out = {}
        for k, (kind, v, s) in entry.items():
            out[k] = v.astype(np.float32) * s if kind == "int8" else v
        return out

    def checksum(self, key: Key) -> int:
        """Reference checksum of ``key``'s dequantized payload (lazily
        computed on first ask, cached until ``put`` overwrites)."""
        if key not in self._checksums:
            self._checksums[key] = payload_checksum(self.fetch(key))
        return self._checksums[key]

    def verify(self, key: Key, weights: dict) -> bool:
        """True iff ``weights`` is a faithful delivery of ``key``'s
        payload (checksums match). Under fault injection every
        delivered fetch is verified; a corrupted copy fails here and
        is refetched (see ``ExpertCache._install``)."""
        return payload_checksum(weights) == self.checksum(key)

    def expert_nbytes(self, key: Key) -> int:
        entry = self._data[key]
        n = 0
        for kind, v, s in entry.values():
            n += v.nbytes + (s.nbytes if s is not None else 0)
        return n

    def total_nbytes(self) -> int:
        return sum(self.expert_nbytes(k) for k in self._data)

    def keys(self):
        return list(self._data)

    def __contains__(self, key):
        return key in self._data

    @classmethod
    def from_params(cls, params, cfg, *, quant: str = "none") -> "ExpertStore":
        """Strip the per-layer expert weights out of a stacked model
        param tree into a store. Expects ``params['layers']['moe']``
        with stacked experts [L, E, ...]."""
        store = cls(quant=quant)
        experts = params["layers"]["moe"]["experts"]
        L = experts["w1"].shape[0]
        E = experts["w1"].shape[1]
        w1 = np.asarray(experts["w1"])
        w2 = np.asarray(experts["w2"])
        w3 = np.asarray(experts["w3"])
        for l in range(L):
            for e in range(E):
                store.put((l, e), {"w1": w1[l, e], "w3": w3[l, e], "w2": w2[l, e]})
        return store
