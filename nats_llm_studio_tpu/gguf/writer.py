"""GGUF v3 writer.

Used for test fixtures (SURVEY.md §4.1: "tiny hand-built GGUF fixtures"),
for converting HF/safetensors checkpoints into the Object Store distribution
format, and for re-quantizing models.
"""

from __future__ import annotations

import struct
from pathlib import Path
from typing import Any

import numpy as np

from .constants import (
    GGUF_DEFAULT_ALIGNMENT,
    GGUF_MAGIC,
    GGUF_VERSION,
    KEY_ALIGNMENT,
    SCALAR_FMT as _SCALAR_FMT,
    GGMLType,
    GGUFValueType,
)
from .quants import quantize, type_size


def _guess_vtype(v: Any) -> GGUFValueType:
    if isinstance(v, bool):
        return GGUFValueType.BOOL
    if isinstance(v, int):
        return GGUFValueType.INT64 if v < 0 else GGUFValueType.UINT32 if v < 2**32 else GGUFValueType.UINT64
    if isinstance(v, float):
        return GGUFValueType.FLOAT32
    if isinstance(v, str):
        return GGUFValueType.STRING
    if isinstance(v, (list, tuple, np.ndarray)):
        return GGUFValueType.ARRAY
    raise TypeError(f"cannot infer GGUF value type for {type(v)}")


class GGUFWriter:
    def __init__(self, path: str | Path, alignment: int = GGUF_DEFAULT_ALIGNMENT):
        self.path = Path(path)
        self.alignment = alignment
        self._kv: list[tuple[str, GGUFValueType, Any, GGUFValueType | None]] = []
        self._tensors: list[tuple[str, tuple[int, ...], GGMLType, bytes]] = []
        self.add(KEY_ALIGNMENT, alignment, GGUFValueType.UINT32)

    def add(self, key: str, value: Any, vtype: GGUFValueType | None = None, elem_type: GGUFValueType | None = None) -> None:
        vtype = vtype if vtype is not None else _guess_vtype(value)
        self._kv.append((key, vtype, value, elem_type))

    def add_dict(self, kv: dict[str, Any]) -> None:
        for k, v in kv.items():
            self.add(k, v)

    def add_tensor(self, name: str, array: np.ndarray, ggml_type: GGMLType | None = None) -> None:
        """Queue a tensor; float arrays are encoded as ``ggml_type``
        (default F32). Logical row-major shape is preserved (reader reverses
        GGUF's dim order back)."""
        if ggml_type is None:
            ggml_type = GGMLType.F32
        arr = np.asarray(array)
        self.add_encoded(name, arr.shape, ggml_type, quantize(arr, ggml_type))

    def add_encoded(self, name: str, shape: tuple[int, ...], ggml_type: GGMLType, data: bytes) -> None:
        """Queue a tensor that is already encoded as ``ggml_type``. The
        writer keeps a reference, not a copy: a caller that queues the same
        ``data`` under many names holds it once."""
        assert len(data) == type_size(ggml_type, int(np.prod(shape, dtype=np.int64)))
        self._tensors.append((name, tuple(shape), ggml_type, data))

    # -- serialization ------------------------------------------------------

    def _w_string(self, out: list[bytes], s: str) -> None:
        b = s.encode("utf-8")
        out.append(struct.pack("<Q", len(b)))
        out.append(b)

    def _w_value(self, out: list[bytes], vtype: GGUFValueType, v: Any, elem_type: GGUFValueType | None) -> None:
        if vtype == GGUFValueType.BOOL:
            out.append(struct.pack("<B", 1 if v else 0))
        elif vtype == GGUFValueType.STRING:
            self._w_string(out, v)
        elif vtype == GGUFValueType.ARRAY:
            seq = v.tolist() if isinstance(v, np.ndarray) else list(v)
            et = elem_type
            if et is None:
                et = _guess_vtype(seq[0]) if seq else GGUFValueType.INT32
                if et == GGUFValueType.UINT64:
                    et = GGUFValueType.INT64
                if all(type(x) is int for x in seq) and seq:
                    et = GGUFValueType.INT32 if all(-(2**31) <= x < 2**31 for x in seq) else GGUFValueType.INT64
            out.append(struct.pack("<I", int(et)))
            out.append(struct.pack("<Q", len(seq)))
            for x in seq:
                self._w_value(out, et, x, None)
        else:
            out.append(struct.pack(_SCALAR_FMT[vtype], v))

    def write(self) -> Path:
        out: list[bytes] = [
            struct.pack("<IIQQ", GGUF_MAGIC, GGUF_VERSION, len(self._tensors), len(self._kv))
        ]
        for key, vtype, v, et in self._kv:
            self._w_string(out, key)
            out.append(struct.pack("<I", int(vtype)))
            self._w_value(out, vtype, v, et)

        # tensor index: dims stored reversed (ne[0] = contiguous axis)
        rel = 0
        for name, shape, ttype, data in self._tensors:
            self._w_string(out, name)
            dims = tuple(reversed(shape)) if shape else (1,)
            out.append(struct.pack("<I", len(dims)))
            for d in dims:
                out.append(struct.pack("<Q", d))
            out.append(struct.pack("<I", int(ttype)))
            out.append(struct.pack("<Q", rel))
            rel += len(data)
            rel = (rel + self.alignment - 1) // self.alignment * self.alignment

        header = b"".join(out)
        pad = (-len(header)) % self.alignment
        with open(self.path, "wb") as f:
            f.write(header)
            f.write(b"\x00" * pad)
            written = 0
            for _, _, _, data in self._tensors:
                f.write(data)
                written += len(data)
                tail = (-written) % self.alignment
                f.write(b"\x00" * tail)
                written += tail
        return self.path
