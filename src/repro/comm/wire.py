"""Fused compressed wire path: the host-plane chunnel that makes
``use_kernel=True`` real (docs/architecture.md §8, ROADMAP direction 1).

The gradient-compression step chunnel models its int8 wire ratio; this module
actually SHIPS the compressed bytes over the host fabric. The whole batch of
float messages is flattened host-side, then one jitted device program fuses
quantize → pack-to-bytes (int8 payload + bitcast fp32 scales into a single
uint8 vector); the receive side runs the inverse unpack → dequantize in one
program and splits back into per-message arrays. ``use_kernel=True`` routes
the quantize/dequantize through the Pallas TPU kernels in
``repro.kernels.quantize`` (compiled on TPU, interpreted on the CPU platform);
``use_kernel=False`` is the pure-jnp oracle — tier-1 tests assert the two
produce identical wire bytes in interpret mode.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax import numpy as jnp

from repro.comm.compress import int8_wire_ratio
from repro.core.capability import CapabilitySet
from repro.core.chunnel import Chunnel, Datapath, WireType
from repro.core.cost import CostModel
from repro.kernels.quantize.quantize import dequantize_blocks, quantize_blocks
from repro.obs.trace import TRACER

TENSOR = WireType.of("tensor", dtype="f32")
BYTES = WireType.of("bytes")

# blob ids only disambiguate concurrent reassembly on one receiving datapath;
# process-global uniqueness is plenty
_BLOB_IDS = itertools.count(1)
_BLOB_LOCK = threading.Lock()


def _next_blob_id() -> int:
    with _BLOB_LOCK:
        return next(_BLOB_IDS)


@functools.partial(jax.jit, static_argnames=("block", "use_kernel", "interpret"))
def _fused_encode(x2d: jnp.ndarray, *, block: int, use_kernel: bool,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """(n_blocks, block) f32 -> one uint8 vector: int8 payload then bitcast
    fp32 scales. One device program for the whole batch. ``interpret`` is the
    kernel's (see ``repro.kernels.interpret_mode``)."""
    if use_kernel:
        q, s = quantize_blocks(x2d, block=block, interpret=interpret)
    else:
        amax = jnp.max(jnp.abs(x2d), axis=1)
        s = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(x2d / s[:, None]), -127, 127).astype(jnp.int8)
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8).reshape(-1)
    sb = jax.lax.bitcast_convert_type(s, jnp.uint8).reshape(-1)
    return jnp.concatenate([qb, sb])


@functools.partial(jax.jit,
                   static_argnames=("n_blocks", "block", "use_kernel", "interpret"))
def _fused_decode(packed: jnp.ndarray, *, n_blocks: int, block: int,
                  use_kernel: bool, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Inverse of ``_fused_encode``: uint8 vector -> flat f32 of length
    n_blocks * block, again one device program."""
    qb = packed[: n_blocks * block].reshape(n_blocks, block)
    q = jax.lax.bitcast_convert_type(qb, jnp.int8)
    sb = packed[n_blocks * block:].reshape(n_blocks, 4)
    s = jax.lax.bitcast_convert_type(sb, jnp.float32)
    if use_kernel:
        out = dequantize_blocks(q, s, block=block, interpret=interpret)
    else:
        out = q.astype(jnp.float32) * s[:, None]
    return out.reshape(-1)


def chunk_payload(payload: bytes, hdr: dict, *,
                  chunk_bytes: int = 1 << 16) -> List[dict]:
    """Split one blob into MTU-sized ``{"_wire": (id, k, n), "hdr", "data"}``
    fabric frames (header rides chunk 0 only). The generic framing layer under
    both the compressed wire path and the WAN link chunnel.

    When tracing is enabled, the sender's current trace ctx rides the header
    (``hdr["tc"]``) so the receive side can stitch reassembly — and eviction
    under loss — back to the span that sent the blob."""
    if TRACER.enabled:
        tc = TRACER.ctx()
        if tc is not None:
            hdr = dict(hdr)  # never mutate the caller's header
            hdr["tc"] = tc
    blob_id = _next_blob_id()
    n_chunks = max(1, -(-len(payload) // chunk_bytes))
    return [{"_wire": (blob_id, k, n_chunks),
             "hdr": hdr if k == 0 else None,
             "data": payload[k * chunk_bytes:(k + 1) * chunk_bytes]}
            for k in range(n_chunks)]


def encode_batch(msgs: List[Any], *, block: int = 256, use_kernel: bool = True,
                 chunk_bytes: int = 1 << 16) -> List[dict]:
    """Batch of float arrays -> wire frames. One host concat, one fused
    device call, then chunking into ``chunk_bytes`` fabric frames."""
    arrs = [np.asarray(m, dtype=np.float32) for m in msgs]
    shapes = [a.shape for a in arrs]
    total = int(sum(a.size for a in arrs))
    if total:
        flat = np.concatenate([a.reshape(-1) for a in arrs])
        pad = (-total) % block
        if pad:
            flat = np.pad(flat, (0, pad))
        x2d = flat.reshape(-1, block)
        packed = _fused_encode(jnp.asarray(x2d), block=block, use_kernel=use_kernel)
        payload = np.asarray(packed, dtype=np.uint8).tobytes()
        n_blocks = x2d.shape[0]
    else:
        payload = b""
        n_blocks = 0
    hdr = {"shapes": [tuple(s) for s in shapes], "block": block,
           "n_blocks": n_blocks}
    return chunk_payload(payload, hdr, chunk_bytes=chunk_bytes)


def decode_blob(payload: bytes, hdr: dict, *, use_kernel: bool = True) -> List[np.ndarray]:
    """Reassembled payload + header -> the original batch (dequantized)."""
    shapes = hdr["shapes"]
    n_blocks, block = hdr["n_blocks"], hdr["block"]
    if n_blocks:
        packed = jnp.asarray(np.frombuffer(payload, dtype=np.uint8))
        flat = np.asarray(_fused_decode(packed, n_blocks=n_blocks, block=block,
                                        use_kernel=use_kernel))
    else:
        flat = np.zeros((0,), dtype=np.float32)
    out: List[np.ndarray] = []
    off = 0
    for shp in shapes:
        size = int(np.prod(shp)) if shp else 1
        out.append(flat[off:off + size].reshape(shp))
        off += size
    return out


class Reassembler:
    """Bounded reassembly of ``chunk_payload`` frames into whole blobs.

    ``ingest`` returns ``(payload, hdr)`` when a blob completes, else None.
    At most ``max_partial`` blobs are held; under sustained frame loss (or a
    partition mid-blob) the oldest partial is evicted, so reassembly state
    stays bounded no matter how hostile the link. Single-consumer, like the
    datapaths that own it."""

    def __init__(self, max_partial: int = 64):
        self.max_partial = max_partial
        self._partial: Dict[int, dict] = {}
        self._order: deque = deque()
        self.evicted = 0  # partial blobs dropped at the bound

    def ingest(self, frame: Any) -> Optional[tuple]:
        if not (isinstance(frame, dict) and "_wire" in frame):
            return None
        blob_id, k, n_chunks = frame["_wire"]
        st = self._partial.get(blob_id)
        if st is None:
            st = {"hdr": None, "chunks": {}, "n": n_chunks}
            self._partial[blob_id] = st
            self._order.append(blob_id)
            while len(self._order) > self.max_partial:
                victim = self._partial.pop(self._order.popleft(), None)
                if victim is not None:
                    self.evicted += 1
                    if TRACER.enabled:
                        # close the sender's span story: the blob died here
                        TRACER.event(
                            "wire.evicted",
                            attrs={"drop_reason": "reassembly_overflow",
                                   "chunks_held": len(victim["chunks"])},
                            ctx=(victim.get("hdr") or {}).get("tc"))
        if frame.get("hdr") is not None:
            st["hdr"] = frame["hdr"]
        st["chunks"][k] = frame["data"]
        if st["hdr"] is not None and len(st["chunks"]) == st["n"]:
            self._partial.pop(blob_id, None)
            payload = b"".join(st["chunks"][i] for i in range(st["n"]))
            return payload, st["hdr"]
        return None

    def partial_count(self) -> int:
        return len(self._partial)


@dataclass
class CompressChunnel(Chunnel):
    """Host-plane int8 compressed wire format (exact-match capability: every
    peer must speak it). ``use_kernel=True`` is the Pallas path (interpreted
    on the CPU platform); ``False`` the jnp oracle — same bytes either way."""

    block: int = 256
    use_kernel: bool = True
    chunk_bytes: int = 1 << 16

    upper_type = TENSOR
    lower_type = BYTES
    multilateral = True

    @property
    def name(self) -> str:
        return f"CompressWire[b{self.block}]"

    def capabilities(self) -> CapabilitySet:
        return CapabilitySet.exact(f"wire:int8-blockq{self.block}")

    def cost_model(self) -> CostModel:
        return CostModel(op_latency_s=5e-4,
                         dcn_bytes_per_byte=int8_wire_ratio(self.block),
                         switch_blip_s=1e-3)

    def connect_wrap(self, inner: Optional[Datapath]) -> Datapath:
        return _CompressDP(self, inner)


class _CompressDP(Datapath):
    """Fused-wire datapath: encode the whole batch in one device call, chunk,
    and reassemble/decode on the receive side."""

    MAX_PARTIAL = 64  # bound reassembly state under frame loss

    def __init__(self, ch: CompressChunnel, inner: Optional[Datapath]):
        self.ch = ch
        self.inner = inner
        self._reasm = Reassembler(max_partial=self.MAX_PARTIAL)
        self._ready: deque = deque()

    def send(self, msgs):
        msgs = list(msgs)
        if not msgs:
            return
        frames = encode_batch(msgs, block=self.ch.block,
                              use_kernel=self.ch.use_kernel,
                              chunk_bytes=self.ch.chunk_bytes)
        if self.inner is not None:
            self.inner.send(frames)

    def recv(self, buf, timeout=None):
        n_out = self._drain(buf, 0)
        if self.inner is None:
            return n_out
        tmp: List[Any] = [None] * max(len(buf), 8)
        deadline = None if timeout is None else time.monotonic() + timeout
        while n_out < len(buf):
            if n_out:
                t: Optional[float] = 0.0  # drain-only once delivering
            elif deadline is None:
                t = None
            else:
                t = deadline - time.monotonic()
                if t <= 0:
                    break  # partial blobs are kept for the next call
            got = self.inner.recv(tmp, t)
            if not got:
                break
            for k in range(got):  # reassemble chunked blobs
                self._ingest(tmp[k])
            n_out = self._drain(buf, n_out)
        return n_out

    def _ingest(self, frame) -> None:
        done = self._reasm.ingest(frame)
        if done is not None:
            payload, hdr = done
            if TRACER.enabled:
                # parented to the SENDER's span via the header trace ctx:
                # this is where a trace crosses chunking + reassembly
                TRACER.event("wire.reassembled",
                             attrs={"bytes": len(payload),
                                    "msgs": len(hdr.get("shapes") or ())},
                             ctx=hdr.get("tc"))
            self._ready.extend(decode_blob(payload, hdr,
                                           use_kernel=self.ch.use_kernel))

    def _drain(self, buf, n_out: int) -> int:
        while n_out < len(buf) and self._ready:
            buf[n_out] = self._ready.popleft()
            n_out += 1
        return n_out
