"""The chunk program's outputs, brought to the host in one copy.

A device->host copy costs a fixed fraction of a millisecond of host time
whatever its size, and a chunk has a dozen small outputs (the per-tick
metrics, the selection and expiry masks, the paging counters, and what
the trace, audit and diagnostics planes add).  So the chunk program lays
every output of its ``ys`` dict out in one flat ``uint32`` buffer
(:func:`pack`, traced at the end of the program), and the host copies
that buffer once and reads each output as a numpy view of the copy
(:meth:`ChunkOutputs.to_host`).

Layout, derived from the shapes and dtypes of ``ys`` alone: the outputs
in key order, each starting at a word boundary.  A 4-byte type (f32,
i32, u32) is bit-cast to words; a 1- or 2-byte type (bool as bytes of
0/1, int8, bf16, ...) is bit-cast to unsigned integers of its width and
packed into words lowest lane first, so on a little-endian host its
bytes lie in order.  Only bit casts: every bit of every value, NaN
payloads and -0.0 included, reaches the host as the program computed it.
"""
from __future__ import annotations

import functools
from collections.abc import Mapping
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class Segment(NamedTuple):
    """Where one output lies in the packed buffer."""
    key: str
    dtype: np.dtype
    shape: Tuple[int, ...]
    offset: int                # bytes from the buffer's start, a multiple of 4

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * self.dtype.itemsize

    @property
    def words(self) -> int:
        return -(-self.nbytes // 4)


def _to_words(x: jax.Array) -> jax.Array:
    """Traceable: ``x`` flattened and bit-cast into ``uint32`` words."""
    x = x.reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    if size == 4:
        return lax.bitcast_convert_type(x, jnp.uint32)
    if size not in (1, 2):
        raise TypeError(f"cannot pack a {x.dtype} output into 32-bit words")
    lanes = 4 // size
    x = lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * size}"))
    x = jnp.pad(x, (0, -x.size % lanes)).reshape(-1, lanes)
    shift = jnp.arange(lanes, dtype=jnp.uint32) * (8 * size)
    # the lanes' bits are disjoint, so the sum is their bitwise or
    return jnp.sum(x.astype(jnp.uint32) << shift, axis=1, dtype=jnp.uint32)


def pack(ys: Dict[str, jax.Array]) -> "ChunkOutputs":
    """Traceable: ``ys`` laid out in one ``uint32`` buffer (see the module
    docstring)."""
    with jax.named_scope("round_metrics"):
        layout, words, offset = [], [], 0
        for key in sorted(ys):
            x = jnp.asarray(ys[key])
            seg = Segment(key, np.dtype(x.dtype), tuple(x.shape), offset)
            layout.append(seg)
            words.append(_to_words(x))
            offset += 4 * seg.words
        buf = (jnp.concatenate(words) if words
               else jnp.zeros((0,), jnp.uint32))
    return ChunkOutputs(buf, tuple(layout))


def packed(step, name: str):
    """``step`` (traceable, ``(*args) -> (final, ys)``) with its ``ys``
    packed, to be jitted; the compiled module is named ``jit_<name>``."""
    def run(*args):
        final, ys = step(*args)
        return final, pack(ys)
    run.__name__ = name
    return run


def _host_views(words: np.ndarray, layout) -> Dict[str, np.ndarray]:
    raw = words.view(np.uint8)
    return {s.key: raw[s.offset:s.offset + s.nbytes].view(s.dtype)
            .reshape(s.shape) for s in layout}


@functools.partial(jax.jit, static_argnums=1)
def _device_view(buf: jax.Array, seg: Segment) -> jax.Array:
    """One output sliced from the device buffer: :func:`_to_words` undone."""
    start = seg.offset // 4
    w = buf[start:start + seg.words]
    size = seg.dtype.itemsize
    if size == 4:
        return lax.bitcast_convert_type(w, seg.dtype).reshape(seg.shape)
    lanes = 4 // size
    shift = jnp.arange(lanes, dtype=jnp.uint32) * (8 * size)
    n = seg.nbytes // size
    u = ((w[:, None] >> shift) & (2 ** (8 * size) - 1)).reshape(-1)[:n]
    u = u.astype(jnp.dtype(f"uint{8 * size}"))
    x = (u.astype(jnp.bool_) if seg.dtype == np.bool_
         else lax.bitcast_convert_type(u, seg.dtype))
    return x.reshape(seg.shape)


@jax.tree_util.register_pytree_node_class
class ChunkOutputs(Mapping):
    """A chunk's outputs packed in one buffer.

    A pytree whose one leaf is the buffer (so ``jax.block_until_ready``
    waits on one array), and a read-only mapping from each output's key
    to an :class:`OutputView` of it."""

    def __init__(self, buf, layout: Tuple[Segment, ...]):
        self.buf = buf
        self.layout = layout
        self._seg = {s.key: s for s in layout}
        self._host = None

    def tree_flatten(self):
        return (self.buf,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, leaves):
        return cls(leaves[0], layout)

    def __getitem__(self, key: str) -> "OutputView":
        return OutputView(self, self._seg[key])

    def __iter__(self):
        return iter(self._seg)

    def __len__(self) -> int:
        return len(self._seg)

    def __contains__(self, key) -> bool:
        return key in self._seg

    def _views(self, copy) -> Dict[str, np.ndarray]:
        if self._host is None:
            self._host = _host_views(copy(self.buf), self.layout)
        return self._host

    def to_host(self, copy=np.asarray) -> Dict[str, np.ndarray]:
        """Every output as a numpy view of one host copy of the buffer,
        made by ``copy`` on the first call (from this or a view) and
        shared by the later ones."""
        return dict(self._views(copy))


class OutputView:
    """One output of a :class:`ChunkOutputs`.

    ``np.asarray`` reads it from the buffer's one host copy (making that
    copy if none exists yet); JAX code that touches it (``jnp.asarray``,
    indexing, ``.at``) gets a device array sliced from the device
    buffer."""

    __slots__ = ("outputs", "segment")

    def __init__(self, outputs: ChunkOutputs, segment: Segment):
        self.outputs = outputs
        self.segment = segment

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.segment.shape

    @property
    def dtype(self) -> np.dtype:
        return self.segment.dtype

    def host(self, copy=np.asarray) -> np.ndarray:
        return self.outputs._views(copy)[self.segment.key]

    def __array__(self, dtype=None, copy=None):
        return np.array(self.host(), dtype=dtype, copy=copy)

    def __jax_array__(self) -> jax.Array:
        return _device_view(self.outputs.buf, self.segment)

    def __getitem__(self, idx):
        return self.__jax_array__()[idx]

    @property
    def at(self):
        return self.__jax_array__().at

    def block_until_ready(self) -> "OutputView":
        jax.block_until_ready(self.outputs.buf)
        return self

    def __repr__(self) -> str:
        s = self.segment
        return f"OutputView({s.key!r}, {s.dtype}{list(s.shape)})"


def copy_to_host(ys, prof) -> Dict[str, np.ndarray]:
    """A chunk's outputs on the host, each copy counted by ``prof``.

    :class:`ChunkOutputs` take one copy of the packed buffer, and count
    their outputs as ``packed_outputs``.  A plain dict (a step wrapper
    that rebuilt the outputs) takes one copy per value, and the views of
    one buffer share that buffer's copy."""
    if isinstance(ys, ChunkOutputs):
        prof.packed(len(ys))
        return ys.to_host(prof.to_host)
    return {k: v.host(prof.to_host) if isinstance(v, OutputView)
            else prof.to_host(v) for k, v in ys.items()}
