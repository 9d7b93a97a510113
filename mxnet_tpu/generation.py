"""Continuous-batching generative inference: paged KV cache + token scheduler.

The reference framework had no autoregressive serving story at all; this
module is the TPU-native one (docs/GENERATIVE.md).  Two ideas carry all the
throughput, borrowed from Orca (iteration-level scheduling, OSDI'22) and
vLLM/PagedAttention (block-allocated KV memory, SOSP'23):

* **Paged KV cache** — K/V live in fixed-size pages ``[L, P, page_size, H,
  D]`` handed out by a host-side free-list allocator
  (:class:`PageAllocator`).  HBM scales with tokens actually generated, not
  ``max_len x max_batch``.  Page 0 is the reserved garbage page: writes from
  prompt padding and inactive decode slots land there unconditionally, so
  the device code never branches on validity.  Occupancy is published on the
  ``gen.kv_page_util`` gauge and exhaustion sheds with a typed
  :class:`~mxnet_tpu.serving.Overloaded` — never an OOM.

* **Token-level continuous batching** — :class:`GenerationServer` runs one
  scheduler thread whose unit of work is a single decode iteration.
  Sequences join (via prefill) and leave (EOS / length / deadline) the
  running batch at iteration boundaries.  Decode shapes are quantized to a
  fixed slot-count bucket chain (the ``MXNET_SHAPE_BUCKETS`` discipline,
  `dispatch.pow2_chain`) with active-slot masks, and
  :meth:`GenerationEngine.warm` compiles every bucket up front — so
  join/leave churn causes **zero recompiles** after warmup (asserted by the
  tests via the ``recompile`` dispatch counter).

The request handle is :class:`~mxnet_tpu.serving.StreamingFuture`: tokens
stream out per iteration, and the serving layer's outcome contract is
preserved verbatim — every admitted request gets exactly one typed terminal
outcome (`Overloaded` / `DeadlineExceeded` / `Draining` / success),
including under drain and SIGTERM preemption.

Model-side compute lives in ``models/transformer.py`` (``prefill`` /
``decode_step``); everything here is host-side orchestration.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import struct
import threading
import time
import zlib

import numpy as np

from . import chaos as _chaos
from . import clock as _clockmod
from . import dispatch as _dispatch
from . import leakcheck as _leakcheck
from . import profiler as _profiler
from . import telemetry as _telemetry
from . import tenancy as _tenancy
from .serving import (DRAINING, SERVING, STARTING, STOPPED, DeadlineExceeded,
                      Draining, Overloaded, QuotaExceeded, StreamingFuture,
                      StreamMigrated, brownout)

__all__ = ["GenerationConfig", "PageAllocator", "GenerationEngine",
           "GenerationServer", "parse_priority", "pack_kv_blob",
           "unpack_kv_blob", "KV_BLOB_MAGIC", "KV_BLOB_VERSION"]

_DEF_PAGE_SIZE = int(os.environ.get("MXTPU_GEN_PAGE_SIZE", "16"))
_DEF_MAX_PAGES = int(os.environ.get("MXTPU_GEN_MAX_PAGES", "256"))
_DEF_MAX_SLOTS = int(os.environ.get("MXTPU_GEN_MAX_SLOTS", "8"))
_DEF_MAX_NEW = int(os.environ.get("MXTPU_GEN_MAX_NEW", "128"))
_DEF_MAX_QUEUE = int(os.environ.get("MXTPU_GEN_MAX_QUEUE", "64"))
_DEF_DEADLINE_MS = float(os.environ.get("MXTPU_GEN_DEADLINE_MS", "60000"))
_DEF_SLOT_BUCKETS = os.environ.get("MXTPU_GEN_SLOT_BUCKETS", "")
_DEF_PREFILL_BUCKETS = os.environ.get("MXTPU_GEN_PREFILL_BUCKETS", "")
_DEF_TEMPERATURE = float(os.environ.get("MXTPU_GEN_TEMPERATURE", "0"))
_DEF_TOP_K = int(os.environ.get("MXTPU_GEN_TOP_K", "0"))
_DEF_SEED = int(os.environ.get("MXTPU_GEN_SEED", "0"))
# live KV migration (docs/SHARDED_SERVING.md "Live migration"): how long
# a parked/imported stream may hold its pages before the TTL sweep frees
# them (an abandoned transfer must not leak KV pages)
_DEF_MIGRATE_PARK_S = float(os.environ.get(
    "MXTPU_MIGRATE_PARK_TIMEOUT_S", "30"))


def _log(msg):
    print("[mxnet_tpu.generation] %s" % msg, flush=True)


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Knobs for the generative stack (env defaults: ``MXTPU_GEN_*``,
    docs/ENV_VARS.md)."""

    page_size: int = _DEF_PAGE_SIZE     # tokens per KV page
    max_pages: int = _DEF_MAX_PAGES     # total pages incl. the garbage page
    max_slots: int = _DEF_MAX_SLOTS     # concurrent decode sequences
    max_new_tokens: int = _DEF_MAX_NEW  # per-request generation cap
    max_seq_len: int = 0                # 0 -> model config max_len
    # bucket chains ('' -> pow2 chain capped at max_slots / max_seq_len)
    slot_buckets: str = _DEF_SLOT_BUCKETS
    prefill_buckets: str = _DEF_PREFILL_BUCKETS
    eos_id: int = -1                    # -1 -> no EOS stopping
    temperature: float = _DEF_TEMPERATURE  # <= 0 -> greedy argmax
    top_k: int = _DEF_TOP_K             # 0 -> full vocabulary
    seed: int = _DEF_SEED               # base seed for per-request rngs


def _resolve_chain(spec, cap):
    """Concrete ascending bucket chain from a comma spec, capped (and
    capped-member-included) so warmup can enumerate every compile."""
    cap = int(cap)
    if spec:
        vals = {int(t) for t in str(spec).split(",") if str(t).strip()}
        vals = {v for v in vals if 0 < v <= cap}
        vals.add(cap)
        return tuple(sorted(vals))
    return _dispatch.pow2_chain(cap)


def _pick_bucket(chain, n):
    for b in chain:
        if b >= n:
            return b
    return chain[-1]


# hostile-header hardening for parse_priority: the whole value is
# length-capped, ranks are digit-capped (a 4000-digit "rank" must not
# become a bignum that outranks everything), and class names are
# sanitized to the counter-safe charset before they mint
# `gen.admitted_by_class.<name>` telemetry keys
_PRIO_MAX_LEN = 256
_PRIO_RANK_DIGITS = 9
_PRIO_NAME_MAX = 32
_PRIO_NAME_CHARS = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789._-")


def _prio_rank_of(tail):
    tail = tail.strip()
    body = tail[1:] if tail[:1] in ("+", "-") else tail
    if not body.isdigit() or len(body) > _PRIO_RANK_DIGITS:
        return None
    return int(tail)


def _prio_name_of(name):
    name = name.strip()
    if (not name or len(name) > _PRIO_NAME_MAX
            or not set(name) <= _PRIO_NAME_CHARS):
        return "default"
    return name


def parse_priority(value):
    """Normalize a request priority into ``(class_name, rank)``.

    Higher rank = more important.  Accepted shapes: ``None`` (the default
    class, rank 0), a bare int rank, a ``"name=rank"`` string (the
    ``X-MXTPU-Priority`` wire form, docs/SHARDED_SERVING.md), a bare
    numeric string, or a bare class name (rank 0).  Malformed or hostile
    values — oversized headers, junk/oversized ranks, class names outside
    ``[A-Za-z0-9._-]`` — degrade to the default class/rank 0 rather than
    failing admission (a bad QoS hint must never 500 a request)."""
    if value is None:
        return ("default", 0)
    if isinstance(value, (int, np.integer)):
        r = int(value)
        return ("p%d" % r, r)
    s = str(value).strip()
    if not s or len(s) > _PRIO_MAX_LEN:
        return ("default", 0)
    if "=" in s:
        name, _, tail = s.partition("=")
        rank = _prio_rank_of(tail)
        return (_prio_name_of(name), 0 if rank is None else rank)
    r = _prio_rank_of(s)
    if r is not None:
        return ("p%d" % r, r)
    return (_prio_name_of(s), 0)


def _sample_token(logits, temperature, top_k, rng):
    """Pick the next token id from one logits row (np [V], host-side).

    ``temperature <= 0`` is greedy argmax — the default, bit-identical to
    the pre-sampling decode path.  Otherwise softmax(logits / temperature)
    in f64, optionally restricted to the ``top_k`` highest logits, sampled
    with the request's own ``np.random.Generator`` so a fixed seed gives a
    deterministic token stream regardless of batch composition.
    """
    if temperature <= 0.0:
        return int(np.argmax(logits))
    z = np.asarray(logits, np.float64) / float(temperature)
    if top_k and top_k < z.shape[-1]:
        kth = np.partition(z, -top_k)[-top_k]
        z = np.where(z >= kth, z, -np.inf)
    z = z - z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(z.shape[-1], p=p))


# ---------------------------------------------------------------------------
# KV snapshot wire format (live migration, docs/GENERATIVE.md)
# ---------------------------------------------------------------------------
# Layout (big-endian):
#   magic[4] | version u16 | header_len u32 | header JSON | payload_len u64
#   | payload (raw K block bytes ++ raw V block bytes) | crc32 u32
# The CRC covers header+payload; any magic/version/CRC/shape mismatch is
# a ValueError so the transfer path can fall back to re-prefill — a
# migration can never be worse than the resume-from-journal path.
KV_BLOB_MAGIC = b"MXKV"
KV_BLOB_VERSION = 1


def pack_kv_blob(header, k_block, v_block):
    """Serialize one parked stream: ``header`` (JSON-able dict) plus its
    gathered K/V pages (np arrays ``[L, n_pages, page_size, H, D]``)."""
    k_block = np.ascontiguousarray(k_block)
    v_block = np.ascontiguousarray(v_block)
    header = dict(header)
    header["kv_dtype"] = str(k_block.dtype)
    header["kv_shape"] = list(k_block.shape)
    hbytes = json.dumps(header, sort_keys=True).encode()
    payload = k_block.tobytes() + v_block.tobytes()
    crc = zlib.crc32(hbytes + payload) & 0xFFFFFFFF
    return b"".join([KV_BLOB_MAGIC,
                     struct.pack(">HI", KV_BLOB_VERSION, len(hbytes)),
                     hbytes,
                     struct.pack(">Q", len(payload)),
                     payload,
                     struct.pack(">I", crc)])


def unpack_kv_blob(blob):
    """Validate + parse a :func:`pack_kv_blob` blob.  Returns
    ``(header, k_block, v_block)``; raises ``ValueError`` on any magic /
    version / truncation / checksum mismatch."""
    blob = bytes(blob)
    if len(blob) < 10 or blob[:4] != KV_BLOB_MAGIC:
        raise ValueError("KV blob: bad magic")
    version, hlen = struct.unpack(">HI", blob[4:10])
    if version != KV_BLOB_VERSION:
        raise ValueError("KV blob: version %d != %d"
                         % (version, KV_BLOB_VERSION))
    off = 10
    if len(blob) < off + hlen + 8:
        raise ValueError("KV blob: truncated header")
    hbytes = blob[off:off + hlen]
    off += hlen
    (plen,) = struct.unpack(">Q", blob[off:off + 8])
    off += 8
    if len(blob) != off + plen + 4:
        raise ValueError("KV blob: truncated payload")
    payload = blob[off:off + plen]
    (crc,) = struct.unpack(">I", blob[off + plen:off + plen + 4])
    if crc != (zlib.crc32(hbytes + payload) & 0xFFFFFFFF):
        raise ValueError("KV blob: CRC mismatch")
    try:
        header = json.loads(hbytes)
    except ValueError:
        raise ValueError("KV blob: unparseable header")
    shape = tuple(int(d) for d in header["kv_shape"])
    dtype = np.dtype(header["kv_dtype"])
    n = int(np.prod(shape)) * dtype.itemsize
    if plen != 2 * n:
        raise ValueError("KV blob: payload is %d byte(s), header says "
                         "2x%d" % (plen, n))
    k_block = np.frombuffer(payload[:n], dtype=dtype).reshape(shape)
    v_block = np.frombuffer(payload[n:], dtype=dtype).reshape(shape)
    return header, k_block, v_block


def _restore_rng(state):
    """Rebuild a ``np.random.Generator`` from its journaled
    ``bit_generator.state`` dict — the migrated stream's sampler resumes
    mid-sequence, bitwise (no fast-forward approximation needed)."""
    name = str(state.get("bit_generator", "PCG64"))
    bg = getattr(np.random, name)()
    bg.state = state
    return np.random.Generator(bg)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------
class PageAllocator:
    """Host-side free-list allocator over the KV page pool.

    Page 0 is reserved as the garbage page (see module docstring) and is
    never handed out; capacity is therefore ``num_pages - 1``.  Occupancy
    is published on the ``gen.kv_page_util`` gauge after every alloc/free,
    and the high-water mark is kept for the bench leg.
    """

    def __init__(self, num_pages):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the garbage page)")
        self.num_pages = int(num_pages)
        self._capacity = self.num_pages - 1
        # pop() from the tail -> lowest page ids are handed out first
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._held = []            # impounded by page_pressure chaos
        self._lock = threading.Lock()
        self.peak_util = 0.0

    @property
    def capacity(self):
        return self._capacity

    @property
    def used(self):
        with self._lock:
            return self._capacity - len(self._free)

    def alloc(self, n):
        """Allocate ``n`` pages; returns their ids, or None when the pool
        cannot satisfy the request (all-or-nothing — no partial grants)."""
        with self._lock:
            if n > len(self._free):
                return None
            got = [self._free.pop() for _ in range(int(n))]
        for p in got:
            # leakcheck ledger: one entry per page until it comes back
            # through free() (RL001's kv-pages pair, mirrored at runtime)
            _leakcheck.track("kv_pages", (id(self), p))
        self._publish()
        return got

    def free(self, pages):
        pages = [int(p) for p in pages]
        with self._lock:
            self._free.extend(pages)
        for p in pages:
            _leakcheck.untrack("kv_pages", (id(self), p))
        self._publish()

    def impound(self, frac):
        """Chaos hook (``page_pressure``): move ``frac`` of the current
        free list into a held side-pool so allocation sees artificial
        exhaustion.  Impounded pages count as used on the util gauge.
        Returns how many pages were impounded.

        Hardened edge cases (tests/test_generation.py): ``frac`` is
        clamped to [0, 1] so a malformed plan can never pop past the end
        of a near-empty free list, and repeated impounds accumulate into
        the same side-pool (one ``release()`` returns them all)."""
        with self._lock:
            frac = min(1.0, max(0.0, float(frac)))
            n = min(len(self._free), int(len(self._free) * frac))
            for _ in range(n):
                self._held.append(self._free.pop())
        self._publish()
        return n

    def release(self):
        """Return every impounded page to the free list (end of the
        ``page_pressure`` window).  Returns how many were released.
        Idempotent: a double release (chaos window ending twice, or a
        release racing a drain sweep) finds an empty side-pool and
        returns 0 — pages re-enter the free list exactly once."""
        with self._lock:
            n = len(self._held)
            self._free.extend(self._held)
            self._held = []
        self._publish()
        return n

    @property
    def held(self):
        """Pages currently impounded by chaos (tests/introspection)."""
        with self._lock:
            return len(self._held)

    def min_free(self):
        """Lowest free page id, or None when the pool is exhausted — the
        defrag pass moves a stream only when a lower-numbered page than
        one it occupies is free (free+realloc pops lowest ids first, so
        relocation provably compacts)."""
        with self._lock:
            return min(self._free) if self._free else None

    def _publish(self):
        util = self.used / self._capacity
        if util > self.peak_util:
            self.peak_util = util
        _telemetry.registry().gauge("gen.kv_page_util").set(util)


# ---------------------------------------------------------------------------
# engine: jitted prefill/decode over bucketed shapes
# ---------------------------------------------------------------------------
class _PendingReq:
    """One queued admission (fresh, resumed, or preempted-and-journaled).

    ``tokens`` is the full prefill input: the prompt, plus — for a resumed
    or re-admitted stream — every token already generated, so re-prefill
    reconstructs the exact KV state the dead/preempted incarnation held.
    ``start_new`` counts those already-generated tail tokens (0 for a
    fresh request); ``patient`` marks an internally-preempted stream,
    which requeues on page exhaustion instead of shedding."""

    __slots__ = ("fut", "tokens", "max_new", "sampling", "prio_name",
                 "prio_rank", "start_new", "patient", "tenant")

    def __init__(self, fut, tokens, max_new, sampling, prio_name,
                 prio_rank, start_new=0, patient=False, tenant="anon"):
        self.fut = fut
        self.tokens = tokens
        self.max_new = max_new
        self.sampling = sampling      # (temperature, top_k, rng)
        self.prio_name = prio_name
        self.prio_rank = prio_rank
        self.start_new = start_new
        self.patient = patient
        self.tenant = tenant


class _Seq:
    """One sequence resident in the decode batch (host-side bookkeeping)."""

    __slots__ = ("fut", "table", "n_pages", "length", "last_token",
                 "n_new", "max_new", "prompt_len", "sampling",
                 "prio_name", "prio_rank", "input_tokens", "gen_tokens",
                 "preempted", "tenant")

    def __init__(self, fut, table, n_pages, length, last_token, max_new,
                 prompt_len, sampling, prio_name="default", prio_rank=0,
                 input_tokens=None, start_new=0, tenant="anon"):
        self.fut = fut
        self.table = table            # np [M] int32, padded with 0
        self.n_pages = n_pages        # leading valid entries of table
        self.length = length          # tokens with K/V in the cache
        self.last_token = last_token  # next token to feed decode_step
        self.n_new = start_new + 1    # generated so far, all incarnations
        #                               (prefill emits one)
        self.max_new = max_new
        self.prompt_len = prompt_len
        self.sampling = sampling      # (temperature, top_k, rng)
        self.prio_name = prio_name
        self.prio_rank = prio_rank
        self.input_tokens = input_tokens  # np array actually prefilled
        self.gen_tokens = [last_token]    # sampled by THIS incarnation
        self.preempted = False
        self.tenant = tenant


class GenerationEngine:
    """Owns the paged KV arrays plus the jitted prefill/decode callables.

    Shapes are quantized to fixed bucket chains (prompt length for prefill,
    slot count for decode) and :meth:`warm` compiles every member, so the
    steady state never retraces.  Both callables go through
    `dispatch.TrackedJit` — the same ``recompile`` / ``jit_cache_*``
    counters the rest of the runtime uses — and donate the page arrays so
    XLA updates the cache in place.
    """

    def __init__(self, model, params, config=None):
        import jax.numpy as jnp

        self._jnp = jnp
        self.model = model
        self.params = params
        self.cfg = config or GenerationConfig()
        if model.cfg.has_experts or model.cfg.attention == "mla":
            model._refuse_serving()     # names what serving still refuses
        self.page_size = int(self.cfg.page_size)
        self.max_seq = int(self.cfg.max_seq_len or model.cfg.max_len)
        self.pages_per_seq = -(-self.max_seq // self.page_size)
        self.allocator = PageAllocator(self.cfg.max_pages)
        self.k_pages, self.v_pages = model.init_kv_pages(
            self.cfg.max_pages, self.page_size)
        self.slot_chain = _resolve_chain(self.cfg.slot_buckets,
                                         self.cfg.max_slots)
        self.prefill_chain = _resolve_chain(self.cfg.prefill_buckets,
                                            self.max_seq)
        # donating the page pools makes the cache update in-place; the
        # same on every backend, so CPU tests run the path the chip runs
        self._prefill_jit = _dispatch.TrackedJit(
            self._prefill_fn, donate_argnums=(1, 2), label="gen_prefill")
        self._decode_jit = _dispatch.TrackedJit(
            self._decode_fn, donate_argnums=(1, 2), label="gen_decode")
        # tagged memory accounting (docs/OBSERVABILITY.md): the engine
        # owns the model params and the KV page pool, the two dominant
        # HBM residents of a decode server (weakly held — a collected
        # engine drops out of the mem.* view)
        from . import memory as _memory

        self._mem_handles = (_memory.register("params",
                                              self._mem_params_bytes),
                             _memory.register("kv_pages",
                                              self._mem_kv_bytes))

    def _mem_params_bytes(self):
        import jax

        return sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(self.params))

    def _mem_kv_bytes(self):
        return (getattr(self.k_pages, "nbytes", 0)
                + getattr(self.v_pages, "nbytes", 0))

    def _prefill_fn(self, params, k_pages, v_pages, tokens, length, table):
        return self.model.prefill(params, k_pages, v_pages, tokens, length,
                                  table)

    def _decode_fn(self, params, k_pages, v_pages, tokens, tables, lens,
                   active):
        return self.model.decode_step(params, k_pages, v_pages, tokens,
                                      tables, lens, active)

    def prefill(self, prompt, table):
        """Run one prompt (1-D int array) against pages ``table`` (np [M]);
        returns the next-token logits as np [V]."""
        jnp = self._jnp
        with _profiler.span("engine.prefill.prepare"):
            T = int(prompt.shape[0])
            tpad = _pick_bucket(self.prefill_chain, T)
            toks = np.zeros((1, tpad), np.int32)
            toks[0, :T] = prompt
            args = (jnp.asarray(toks), jnp.int32(T), jnp.asarray(table))
        with _profiler.span("engine.prefill.dispatch"):
            self.k_pages, self.v_pages, logits = self._prefill_jit(
                self.params, self.k_pages, self.v_pages, *args)
        _profiler.dispatch_count("gen_prefills")
        with _profiler.span("engine.prefill.fetch"):
            return np.asarray(logits)

    def decode(self, seqs):
        """One decode iteration over ``seqs`` (list of :class:`_Seq`),
        padded up to the enclosing slot bucket; returns np logits
        [len(seqs), V].  Does NOT advance host bookkeeping — the caller
        owns lengths/tokens so it can settle outcomes under its lock."""
        jnp = self._jnp
        n = len(seqs)
        with _profiler.span("engine.decode.prepare"):
            bucket = _pick_bucket(self.slot_chain, n)
            m = self.pages_per_seq
            toks = np.zeros(bucket, np.int32)
            tables = np.zeros((bucket, m), np.int32)
            lens = np.zeros(bucket, np.int32)
            active = np.zeros(bucket, bool)
            for i, s in enumerate(seqs):
                toks[i] = s.last_token
                tables[i] = s.table
                lens[i] = s.length
                active[i] = True
            args = (jnp.asarray(toks), jnp.asarray(tables),
                    jnp.asarray(lens), jnp.asarray(active))
        with _profiler.span("engine.decode.dispatch"):
            self.k_pages, self.v_pages, logits = self._decode_jit(
                self.params, self.k_pages, self.v_pages, *args)
        _profiler.dispatch_count("gen_decode_iters")
        _profiler.dispatch_count("gen_tokens", n)
        with _profiler.span("engine.decode.fetch"):
            return np.asarray(logits[:n])

    def warm(self):
        """Compile every prefill and decode bucket up front.  All warmup
        writes are routed to the garbage page (zero page tables, inactive
        slots), so no allocation happens and no cache state is disturbed."""
        jnp = self._jnp
        m = self.pages_per_seq
        zt = jnp.zeros(m, jnp.int32)
        for tpad in self.prefill_chain:
            self.k_pages, self.v_pages, _ = self._prefill_jit(
                self.params, self.k_pages, self.v_pages,
                jnp.zeros((1, tpad), jnp.int32), jnp.int32(1), zt)
        for s in self.slot_chain:
            self.k_pages, self.v_pages, _ = self._decode_jit(
                self.params, self.k_pages, self.v_pages,
                jnp.zeros(s, jnp.int32), jnp.zeros((s, m), jnp.int32),
                jnp.zeros(s, jnp.int32), jnp.zeros(s, bool))
        _log("warm: %d prefill bucket(s) %s, %d decode bucket(s) %s"
             % (len(self.prefill_chain), list(self.prefill_chain),
                len(self.slot_chain), list(self.slot_chain)))


# ---------------------------------------------------------------------------
# token-level scheduler
# ---------------------------------------------------------------------------
class GenerationServer:
    """Continuous-batching front end over one :class:`GenerationEngine`.

    A single scheduler thread owns the device: each loop turn it either
    prefills ONE waiting request into a free slot or runs ONE decode
    iteration over the active batch — that alternation IS iteration-level
    scheduling (Orca): joins and leaves only ever happen between decode
    steps.  All outcome settlement (resolve/reject) happens under the
    server lock, exactly like :class:`~mxnet_tpu.serving.ModelServer`, so
    deadline expiry, page shedding, and drain races keep the exactly-once
    typed-outcome contract.  Device compute always runs OUTSIDE the lock.
    """

    def __init__(self, model, params, config=None, *, max_queue=None,
                 deadline_ms=None, warm=True, clock=None):
        self.clock = _clockmod.resolve(clock)
        self.engine = GenerationEngine(model, params, config)
        self.cfg = self.engine.cfg
        self.max_queue = _DEF_MAX_QUEUE if max_queue is None \
            else int(max_queue)
        self.default_deadline = (_DEF_DEADLINE_MS if deadline_ms is None
                                 else float(deadline_ms)) / 1e3
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._pending = collections.deque()   # [_PendingReq]
        self._active = []                     # [_Seq]
        self._inflight = None                 # fut mid-prefill (not yet in
        #                                       _active; drain must see it)
        self._drain_flag = threading.Event()
        self._stop = False
        self._preemption = None
        self._defer_prefill = False           # force one decode turn so a
        #                                       requeued patient prefill
        #                                       cannot starve the batch
        self._loop_turn = 0                   # page_pressure chaos clock
        self._pressure_until = 0
        # live migration (docs/SHARDED_SERVING.md "Live migration"):
        # parked streams awaiting export, imported streams awaiting
        # attach — both hold KV pages under a TTL so an abandoned
        # transfer can never leak them
        self._parked = {}                     # handle -> record
        self._imports = {}                    # handle -> record
        self._park_timeout = _DEF_MIGRATE_PARK_S
        self._tasks = collections.deque()     # (fn, box, evt) run on the
        #                                       scheduler thread (engine
        #                                       arrays have one writer)
        self._limbo = 0                       # seqs mid-defrag-relocation
        self._state = STARTING
        self.stats = {
            "admitted": 0, "shed_queue": 0, "shed_pages": 0, "ok": 0,
            "deadline_exceeded": 0, "rejected_draining": 0,
            "preempted": 0, "resumed": 0, "shed_brownout": 0,
            "shed_quota": 0,
            "parked": 0, "migrated_out": 0, "migrated_in": 0,
            "migrate_attached": 0, "migrate_expired": 0,
            "defrag_moved": 0,
        }
        if warm:
            self.engine.warm()
        self._state = SERVING
        # postmortem bundles embed the scheduler view (weakly held)
        from . import debug as _debug

        _debug.add_section("generation", self.snapshot)
        self._thread = threading.Thread(target=self._loop,
                                        name="gen-scheduler", daemon=True)
        self._thread.start()

    @property
    def state(self):
        with self._lock:
            return self._state

    # -- admission -----------------------------------------------------
    def submit_async(self, prompt, max_new_tokens=None, deadline_ms=None,
                     on_token=None, temperature=None, top_k=None, seed=None,
                     priority=None, resume_from=None, migrate_handle=None,
                     tenant=None):
        """Admit one generation request; returns a
        :class:`~mxnet_tpu.serving.StreamingFuture` or raises the typed
        admission error (:class:`Overloaded` / :class:`Draining` /
        :class:`QuotaExceeded`).

        ``tenant`` is the validated ``X-MXTPU-Tenant`` id (see
        :mod:`mxnet_tpu.tenancy`): admission spends one token from the
        tenant's bucket and — when the queue is contended — holds each
        tenant to its weighted-fair share of queue slots, so a flooding
        tenant sheds typed :class:`QuotaExceeded` while everyone else
        keeps streaming.  ``exempt`` tenants (paying tiers) bypass the
        brownout rank gate and token cap, but never quota/fair-share.

        ``temperature`` / ``top_k`` / ``seed`` override the config-level
        sampling knobs per request (``temperature <= 0`` = greedy argmax,
        ``top_k == 0`` = full vocabulary).  Sampling state is per-request
        and host-side, so batch composition never perturbs a stream: an
        explicit ``seed`` replays the exact token stream; by default each
        request derives an independent rng from ``(cfg.seed, admission
        index)``.

        ``priority`` is any :func:`parse_priority` shape; under page
        exhaustion strictly-lower-rank streams are preempted before
        anything is shed, and brownout level 3 admits only ranks at or
        above the configured floor (docs/GENERATIVE.md).

        ``resume_from`` — a list of tokens an earlier incarnation of this
        stream already generated (gateway failover, docs/
        SHARDED_SERVING.md).  The worker re-prefills prompt+prefix and the
        returned future streams only the continuation.  With an explicit
        ``seed`` the rng is fast-forwarded by ``len(resume_from)`` draws,
        so a sampled resume produces the exact suffix the unkilled run
        would have (greedy mode is bitwise-identical by construction).

        ``migrate_handle`` — a handle returned by :meth:`import_stream`:
        attach directly to the installed KV state (length, last token and
        live sampling rng shipped in the snapshot) with **no prefill at
        all** — the bitwise-continuation guarantee without the O(context)
        recompute.  An unknown/expired handle, or a snapshot that
        disagrees with the caller's journal, silently falls back to the
        ``resume_from`` re-prefill path — migration is never worse than
        failover."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        prefix = (np.asarray(resume_from, np.int32).reshape(-1)
                  if resume_from is not None else None)
        if migrate_handle is not None:
            fut = self._attach_migrated(migrate_handle, prompt, prefix,
                                        max_new_tokens, deadline_ms,
                                        on_token)
            if fut is not None:
                return fut
            # fall through: re-prefill from the journal instead
        start_new = 0 if prefix is None else int(prefix.size)
        tokens = prompt if prefix is None \
            else np.concatenate([prompt, prefix])
        if tokens.size >= self.engine.max_seq:
            raise ValueError("prompt length %d >= max_seq_len %d"
                             % (tokens.size, self.engine.max_seq))
        max_new = int(max_new_tokens or self.cfg.max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if start_new and max_new - start_new < 1:
            raise ValueError("resume_from already carries %d token(s), "
                             ">= max_new_tokens %d" % (start_new, max_new))
        temperature = (self.cfg.temperature if temperature is None
                       else float(temperature))
        top_k = self.cfg.top_k if top_k is None else int(top_k)
        if top_k < 0:
            raise ValueError("top_k must be >= 0")
        prio_name, prio_rank = parse_priority(priority)
        tenant = _tenancy.parse_tenant(tenant)
        gov = _tenancy.governor()
        exempt = gov.exempt(tenant)
        bo = brownout()
        if not exempt:
            max_new = max(bo.cap_max_new(max_new), start_new + 1)
        now = self.clock.now()
        deadline = now + (self.default_deadline if deadline_ms is None
                          else float(deadline_ms) / 1e3)
        with self._cv:
            if (self._drain_flag.is_set()
                    or self._state in (DRAINING, STOPPED)):
                self.stats["rejected_draining"] += 1
                raise Draining("generation server is draining")
            try:
                # fair-share sees the live queue composition: how many
                # slots this tenant already holds, and who else is queued
                # (the pending deque is queue_cap-bounded, so the scan is
                # O(max_queue), not O(traffic))
                gov.check(tenant, now,
                          queue_len=len(self._pending),
                          queue_cap=self.max_queue,
                          tenant_pending=sum(
                              1 for r in self._pending
                              if r.tenant == tenant),
                          queue_tenants={r.tenant
                                         for r in self._pending})
            except QuotaExceeded:
                self.stats["shed_quota"] += 1
                _profiler.dispatch_count("gen_quota_shed")
                _profiler.dispatch_count("gen.shed_by_tenant.%s" % tenant)
                raise
            if not exempt and not bo.admits(prio_rank):
                self.stats["shed_brownout"] += 1
                _profiler.dispatch_count("gen_brownout_shed")
                _profiler.dispatch_count("gen.shed_by_tenant.%s" % tenant)
                raise Overloaded(
                    "brownout level %d admits only priority rank >= %d "
                    "(got %s=%d)" % (bo.level, bo.min_rank, prio_name,
                                     prio_rank))
            if len(self._pending) >= self.max_queue:
                self.stats["shed_queue"] += 1
                _profiler.dispatch_count("requests_shed")
                _profiler.dispatch_count("gen.shed_by_tenant.%s" % tenant)
                raise Overloaded("generation queue full (%d pending)"
                                 % len(self._pending))
            fut = StreamingFuture({"tokens": tokens}, rows=1,
                                  deadline=deadline, t_admit=now,
                                  on_token=on_token, clock=self.clock)
            self.stats["admitted"] += 1
            if start_new:
                self.stats["resumed"] += 1
                _profiler.dispatch_count("gen_resumed")
            _profiler.dispatch_count("requests_admitted")
            _profiler.dispatch_count("gen.admitted_by_class.%s" % prio_name)
            _profiler.dispatch_count("gen.admitted_by_tenant.%s" % tenant)
            _telemetry.trace_begin("request", fut.trace_id, cat="gen",
                                   args={"prompt_len": int(prompt.size),
                                         "max_new": max_new,
                                         "priority": prio_name,
                                         "resumed": start_new})
            rng = np.random.default_rng(
                int(seed) if seed is not None
                else (self.cfg.seed, self.stats["admitted"]))
            if start_new and seed is not None and temperature > 0.0:
                # one uniform draw per sampled token (rng.choice consumes
                # exactly one double) — fast-forward past the prefix so
                # the resumed suffix replays the unkilled stream
                rng.random(start_new)
            self._pending.append(_PendingReq(
                fut, tokens, max_new, (temperature, top_k, rng),
                prio_name, prio_rank, start_new=start_new,
                tenant=tenant))
            self._cv.notify_all()
        return fut

    def submit(self, prompt, timeout=None, **kw):
        """Blocking convenience: the generated token-id list."""
        return self.submit_async(prompt, **kw).result(timeout=timeout)

    # -- live KV migration (docs/SHARDED_SERVING.md "Live migration") --
    @staticmethod
    def _new_handle():
        return "kvm-" + os.urandom(8).hex()

    def _run_on_scheduler(self, fn, timeout=30.0):
        """Run ``fn`` on the scheduler thread and return its result.

        The engine's page arrays have exactly one writer (the scheduler:
        prefill/decode reassign them functionally), so any read-modify-
        write — the import scatter, the defrag relocation — must run
        there too or a concurrent decode's reassignment would silently
        drop the update."""
        if threading.current_thread() is self._thread:
            return fn()
        box = {}
        evt = threading.Event()
        with self._cv:
            if self._stop or self._state == STOPPED:
                raise Draining("generation server is stopped")
            self._tasks.append((fn, box, evt))
            self._cv.notify_all()
        if not evt.wait(timeout):
            raise TimeoutError("scheduler did not service the task "
                               "within %.1fs" % timeout)
        if "error" in box:
            raise box["error"]
        return box["result"]

    # -- adapter hot-multiplexing (docs/SHARDED_SERVING.md) ------------
    def swap_params(self, params):
        """Atomically swap the engine's weights for a same-structure
        adapter — the generation side of the :meth:`ModelServer.reload
        <mxnet_tpu.serving.ModelServer>` hot-swap contract.

        The params pytree must match the resident one leaf-for-leaf in
        structure, shape and dtype; since params are a *traced* argument
        of the jitted prefill/decode callables, a conforming swap reuses
        every compiled executable — zero recompiles, proven by the
        ``recompile`` counter the worker's ``/healthz`` exposes.  The
        assignment runs on the scheduler thread, between decode steps,
        so every in-flight stream sees one coherent set of weights per
        step (tokens sampled before the swap came wholly from the old
        adapter, after it wholly from the new)."""
        import jax

        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.engine.params)
        if new_def != old_def:
            raise ValueError("adapter params tree structure differs from "
                             "the resident model (%s vs %s)"
                             % (new_def, old_def))
        for i, (old, new) in enumerate(zip(old_leaves, new_leaves)):
            os_, ns = tuple(old.shape), tuple(new.shape)
            od, nd = str(old.dtype), str(new.dtype)
            if os_ != ns or od != nd:
                raise ValueError(
                    "adapter params leaf %d is %s%s, resident model has "
                    "%s%s — a swap must be shape/dtype-identical to stay "
                    "recompile-free" % (i, nd, ns, od, os_))

        def _install():
            self.engine.params = params
            return True

        self._run_on_scheduler(_install)
        _profiler.dispatch_count("gen_adapter_swaps")
        _telemetry.trace_instant("gen.adapter_swap", cat="gen",
                                 args={"leaves": len(new_leaves)})
        return True

    def _park_seq_locked(self, seq):
        """Evict ``seq`` from the batch but KEEP its pages: record every
        field a receiver needs for bitwise continuation (page table, host
        cursor, live sampling rng, QoS rank) under a fresh handle, and
        settle the old future with :class:`StreamMigrated` so the worker
        emits a ``migrate`` line instead of tokens.  Caller holds the cv.

        Safe against an in-flight decode: the post-decode advance loop
        skips done futures without touching host state, and a re-run of
        the same decode position writes bitwise-identical KV — so the
        snapshot cursor and the page contents can never disagree."""
        self._active.remove(seq)
        handle = self._new_handle()
        start0 = seq.n_new - len(seq.gen_tokens)
        n_prompt = int(seq.input_tokens.size) - start0
        temperature, top_k, rng = seq.sampling
        rec = {
            "prompt": np.asarray(seq.input_tokens[:n_prompt], np.int32),
            "generated": ([int(t) for t in seq.input_tokens[n_prompt:]]
                          + [int(t) for t in seq.gen_tokens]),
            "input_tokens": seq.input_tokens,
            "gen_tokens": [int(t) for t in seq.gen_tokens],
            "length": int(seq.length),
            "last_token": int(seq.last_token),
            "n_new": int(seq.n_new),
            "max_new": int(seq.max_new),
            "prompt_len": int(seq.prompt_len),
            "temperature": float(temperature),
            "top_k": int(top_k),
            "rng": rng,
            "prio_name": seq.prio_name,
            "prio_rank": int(seq.prio_rank),
            "tenant": seq.tenant,
            "table": seq.table,
            "n_pages": int(seq.n_pages),
            "expires": self.clock.now() + self._park_timeout,
        }
        self._parked[handle] = rec
        self.stats["parked"] += 1
        _profiler.dispatch_count("gen_parked")
        _telemetry.trace_instant(
            "gen.park", cat="gen",
            args={"handle": handle, "tokens": seq.n_new,
                  "pages": seq.n_pages})
        seq.fut._reject(StreamMigrated(
            "stream parked for migration after %d token(s)" % seq.n_new,
            handle=handle))
        self._cv.notify_all()
        return handle

    def park_streams(self, n=None):
        """Park up to ``n`` active streams (all of them by default) for
        migration; returns their handles.  Largest KV footprint first —
        the stream whose move frees the most pages / saves the most
        re-prefill.  Each parked stream's old future settles with
        :class:`StreamMigrated`; the state is claimable via
        :meth:`export_stream` until the park TTL expires."""
        with self._cv:
            cands = [s for s in self._active
                     if not s.fut.done and not s.preempted]
            cands.sort(key=lambda s: (-s.n_pages, -s.n_new))
            if n is not None:
                cands = cands[:max(0, int(n))]
            return [self._park_seq_locked(s) for s in cands]

    def export_stream(self, handle):
        """Serialize a parked stream into the versioned, CRC-checksummed
        wire blob and free its pages on this side (the blob is now the
        only copy — the sender forgets the stream).  Raises ``KeyError``
        for an unknown/expired handle."""
        t0 = time.perf_counter()
        with self._cv:
            rec = self._parked.pop(handle, None)
            if rec is None:
                raise KeyError("unknown or expired migration handle %r"
                               % handle)
        pages = [int(p) for p in rec["table"][:rec["n_pages"]]]
        eng = self.engine

        def gather():
            idx = eng._jnp.asarray(np.asarray(pages, np.int32))
            return (np.asarray(eng.k_pages[:, idx]),
                    np.asarray(eng.v_pages[:, idx]))

        # the page arrays are donated to every prefill/decode call, so a
        # handle captured here could be deleted before it is read: gather
        # on the scheduler thread, between iterations.  The host copy is
        # the only copy from here on, whether or not the gather succeeded
        try:
            k_block, v_block = self._run_on_scheduler(gather)
        finally:
            eng.allocator.free(pages)
        header = {
            "prompt": [int(t) for t in rec["prompt"]],
            "generated": rec["generated"],
            "input_tokens": [int(t) for t in rec["input_tokens"]],
            "gen_tokens": rec["gen_tokens"],
            "length": rec["length"],
            "last_token": rec["last_token"],
            "n_new": rec["n_new"],
            "max_new": rec["max_new"],
            "prompt_len": rec["prompt_len"],
            "temperature": rec["temperature"],
            "top_k": rec["top_k"],
            "rng_state": rec["rng"].bit_generator.state,
            "prio_name": rec["prio_name"],
            "prio_rank": rec["prio_rank"],
            "n_pages": rec["n_pages"],
            "page_size": int(self.engine.page_size),
        }
        blob = pack_kv_blob(header, k_block, v_block)
        with self._cv:
            self.stats["migrated_out"] += 1
        _profiler.dispatch_count("gen_migrated_out")
        _telemetry.registry().histogram("gen.migrate_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        return blob

    def import_stream(self, blob):
        """Validate + install a :meth:`export_stream` blob: allocate
        pages from this server's :class:`PageAllocator` (leak-audited
        like any admission), scatter the KV block into the page arrays
        on the scheduler thread, and stage the stream for
        ``submit_async(migrate_handle=...)`` attach.  Returns the local
        handle.  Raises ``ValueError`` on checksum/version/shape
        mismatch and :class:`Overloaded` when no pages are free — the
        caller falls back to re-prefill either way."""
        t0 = time.perf_counter()
        header, k_block, v_block = unpack_kv_blob(blob)
        eng = self.engine
        n_pages = int(header["n_pages"])
        shape = k_block.shape
        want = tuple(eng.k_pages.shape)
        if (int(header["page_size"]) != eng.page_size
                or shape[0] != want[0] or shape[1] != n_pages
                or shape[2:] != want[2:]
                or str(k_block.dtype) != str(eng.k_pages.dtype)):
            raise ValueError(
                "KV blob: incompatible geometry %s/%s page_size=%s for "
                "engine %s page_size=%d"
                % (shape, k_block.dtype, header["page_size"], want,
                   eng.page_size))
        if n_pages > eng.pages_per_seq \
                or int(header["length"]) >= eng.max_seq:
            raise ValueError("KV blob: %d page(s) / length %d exceed "
                             "this engine's max_seq_len %d"
                             % (n_pages, header["length"], eng.max_seq))

        def install():
            pages = eng.allocator.alloc(n_pages)
            if pages is None:
                raise Overloaded(
                    "KV pages exhausted: migration needs %d page(s), "
                    "%d free of %d" % (n_pages, eng.allocator.capacity
                                       - eng.allocator.used,
                                       eng.allocator.capacity))
            jnp = eng._jnp
            idx = jnp.asarray(np.asarray(pages, np.int32))
            eng.k_pages = eng.k_pages.at[:, idx].set(jnp.asarray(k_block))
            eng.v_pages = eng.v_pages.at[:, idx].set(jnp.asarray(v_block))
            return pages

        pages = self._run_on_scheduler(install)
        table = np.zeros(eng.pages_per_seq, np.int32)
        table[:n_pages] = pages
        handle = self._new_handle()
        rec = {
            "prompt": np.asarray(header["prompt"], np.int32),
            "generated": [int(t) for t in header["generated"]],
            "input_tokens": np.asarray(header["input_tokens"], np.int32),
            "gen_tokens": [int(t) for t in header["gen_tokens"]],
            "length": int(header["length"]),
            "last_token": int(header["last_token"]),
            "n_new": int(header["n_new"]),
            "max_new": int(header["max_new"]),
            "prompt_len": int(header["prompt_len"]),
            "temperature": float(header["temperature"]),
            "top_k": int(header["top_k"]),
            "rng": _restore_rng(header["rng_state"]),
            "prio_name": str(header["prio_name"]),
            "prio_rank": int(header["prio_rank"]),
            "table": table,
            "n_pages": n_pages,
            "expires": self.clock.now() + self._park_timeout,
        }
        with self._cv:
            self._imports[handle] = rec
            self.stats["migrated_in"] += 1
        _profiler.dispatch_count("gen_migrated_in")
        _telemetry.registry().histogram("gen.migrate_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        return handle

    def _attach_migrated(self, handle, prompt, prefix, max_new_tokens,
                         deadline_ms, on_token):
        """Attach a fresh future to an imported stream — the
        ``migrate_handle`` half of :meth:`submit_async`.  Returns the
        future, or None to fall back to the re-prefill path."""
        delivered = [] if prefix is None else [int(t) for t in prefix]
        now = self.clock.now()
        deadline = now + (self.default_deadline if deadline_ms is None
                          else float(deadline_ms) / 1e3)
        with self._cv:
            if (self._drain_flag.is_set()
                    or self._state in (DRAINING, STOPPED)):
                self.stats["rejected_draining"] += 1
                raise Draining("generation server is draining")
            rec = self._imports.get(handle)
            if rec is None:
                return None
            generated = rec["generated"]
            if (not np.array_equal(prompt, rec["prompt"])
                    or len(delivered) > len(generated)
                    or generated[:len(delivered)] != delivered):
                # snapshot and journal disagree: drop the import, free
                # its pages, re-prefill from the journal (never worse)
                del self._imports[handle]
                self.engine.allocator.free(
                    [int(p) for p in rec["table"][:rec["n_pages"]]])
                self.stats["migrate_expired"] += 1
                return None
            del self._imports[handle]
            max_new = int(max_new_tokens or rec["max_new"])
            bo = brownout()
            max_new = max(bo.cap_max_new(max_new), len(generated))
            fut = StreamingFuture({"tokens": rec["input_tokens"]}, rows=1,
                                  deadline=deadline, t_admit=now,
                                  on_token=on_token, clock=self.clock)
            self.stats["admitted"] += 1
            self.stats["migrate_attached"] += 1
            _profiler.dispatch_count("requests_admitted")
            _profiler.dispatch_count("gen_migrate_attached")
            _telemetry.trace_begin("request", fut.trace_id, cat="gen",
                                   args={"migrated": True,
                                         "tokens": len(generated)})
            seq = _Seq(fut, rec["table"], rec["n_pages"], rec["length"],
                       rec["last_token"], max_new, rec["prompt_len"],
                       (rec["temperature"], rec["top_k"], rec["rng"]),
                       prio_name=rec["prio_name"],
                       prio_rank=rec["prio_rank"],
                       input_tokens=rec["input_tokens"],
                       tenant=rec.get("tenant", "anon"))
            seq.gen_tokens = list(rec["gen_tokens"])
            seq.n_new = len(generated)
            gap = generated[len(delivered):]
        # catch-up emission outside the lock (token callbacks are user
        # code) — tokens generated before the park that the client has
        # not seen yet stream first, then decode continues from the KV
        finished = seq.n_new >= seq.max_new
        for t in gap:
            if not fut._emit(int(t)):
                finished = True
                break
        with self._cv:
            if fut.done:                       # deadline/cancel raced
                self.engine.allocator.free(
                    [int(p) for p in seq.table[:seq.n_pages]])
            elif finished:
                self._active.append(seq)
                self._retire_locked(seq)
            else:
                self._active.append(seq)
                self._cv.notify_all()
        return fut

    def release_import(self, handle):
        """Drop a staged (imported, unattached) migration record and free
        its pages — the transfer-abort path (``/v1/migrate_abort``).
        Returns True if the handle was live.  Idempotent."""
        with self._cv:
            rec = self._imports.pop(handle, None)
            if rec is None:
                return False
            pages = [int(p) for p in rec["table"][:rec["n_pages"]]]
            if pages:
                self.engine.allocator.free(pages)
            self.stats["migrate_expired"] += 1
        _profiler.dispatch_count("gen_migrate_expired")
        return True

    def _sweep_migration_locked(self, now):
        """TTL sweep: free the pages of parked/imported streams nobody
        claimed (aborted transfer, dead gateway).  Caller holds the cv."""
        for store in (self._parked, self._imports):
            for h in [h for h, r in store.items() if now >= r["expires"]]:
                rec = store.pop(h)
                pages = [int(p) for p in rec["table"][:rec["n_pages"]]]
                if pages:
                    self.engine.allocator.free(pages)
                self.stats["migrate_expired"] += 1
                _profiler.dispatch_count("gen_migrate_expired")
                _log("migration handle %s expired unclaimed — freed %d "
                     "page(s)" % (h, len(pages)))

    # -- defrag (self-migration) ---------------------------------------
    def defrag(self, timeout=30.0):
        """Compact fragmented page tables by migrating streams to this
        server itself: gather a stream's pages, free them, re-allocate
        (the free list hands out lowest ids first) and scatter back.
        Returns how many streams moved.  Runs on the scheduler thread —
        the only writer of the page arrays — between iterations, so the
        decode loop never sees a half-moved table."""
        return self._run_on_scheduler(self._defrag_pass, timeout=timeout)

    def _defrag_pass(self):
        eng = self.engine
        jnp = eng._jnp
        moved = 0
        with self._cv:
            seqs = [s for s in self._active
                    if not s.fut.done and not s.preempted]
        for s in seqs:
            with self._cv:
                if s not in self._active or s.fut.done or s.preempted:
                    continue
                old = [int(p) for p in s.table[:s.n_pages]]
                low = eng.allocator.min_free()
                if not old or low is None or low >= max(old):
                    continue          # already as compact as it can get
                # take the seq out of the batch while its pages move so
                # a racing retire/park cannot free a stale table
                self._active.remove(s)
                self._limbo += 1
            new = None
            try:
                idx_old = jnp.asarray(np.asarray(old, np.int32))
                k_block = eng.k_pages[:, idx_old]
                v_block = eng.v_pages[:, idx_old]
                eng.allocator.free(old)
                new = eng.allocator.alloc(len(old))
                if new is None:       # cannot happen (just freed n)
                    raise Overloaded("defrag lost its own pages")
                idx_new = jnp.asarray(np.asarray(new, np.int32))
                eng.k_pages = eng.k_pages.at[:, idx_new].set(k_block)
                eng.v_pages = eng.v_pages.at[:, idx_new].set(v_block)
                with self._cv:
                    self._limbo -= 1
                    s.table[:len(new)] = new
                    if s.fut.done:    # settled while relocating: tidy up
                        eng.allocator.free(new)
                    else:
                        self._active.append(s)
                        moved += 1
                        self.stats["defrag_moved"] += 1
                    self._cv.notify_all()
            except BaseException:
                # relocation failed mid-flight: the stream's KV is in an
                # unknown state — give it one typed outcome, return any
                # pages it still holds, and keep the server healthy
                with self._cv:
                    self._limbo -= 1
                    if new:
                        eng.allocator.free(new)
                    self._reject_locked(s.fut, Overloaded(
                        "defrag relocation failed after %d token(s)"
                        % s.n_new))
                continue
        if moved:
            _profiler.dispatch_count("gen_defrag_moved", moved)
            _telemetry.trace_instant("gen.defrag", cat="gen",
                                     args={"moved": moved})
        return moved

    # -- scheduler loop ------------------------------------------------
    def _loop(self):
        while True:
            with _profiler.span("engine.gen.turn") as turn:
                if not self._turn(turn):
                    break
        # scheduler stopped: unblock every waiter still queued behind it
        with self._cv:
            leftovers = list(self._tasks)
            self._tasks.clear()
        for _fn, box, evt in leftovers:
            box["error"] = Draining("scheduler stopped before the "
                                    "migration task ran")
            evt.set()

    def _turn(self, turn):
        """One scheduler turn: admission, then a posted task, one prefill
        or one decode iteration.  False once the scheduler is to stop.  A
        turn that found nothing to do but wait is dropped from the span
        ring, so an idle server does not fill it."""
        work = task = None
        with _profiler.span("engine.gen.admit") as admit:
            with self._cv:
                if self._stop:
                    return False
                if self._drain_flag.is_set() and self._state == SERVING:
                    self._state = DRAINING
                self._expire_locked(self.clock.now())
                self._loop_turn += 1
            self._chaos_pressure()                 # allocator IO, no lock
            with self._cv:
                if self._stop:
                    return False
                if self._tasks:
                    # engine-array work posted by another thread (import
                    # scatter, defrag) — serviced here because this
                    # thread is the page arrays' only writer
                    task = self._tasks.popleft()
                elif (self._pending and not self._defer_prefill
                        and len(self._active) < self.cfg.max_slots):
                    work = self._pending.popleft()
                    self._inflight = work.fut
                elif not self._active:
                    self._defer_prefill = False
                    self._cv.wait(0.02)
                    admit.drop()
                    turn.drop()
                    return True
                else:
                    self._defer_prefill = False
        if task is not None:
            fn, box, evt = task
            try:
                box["result"] = fn()               # device work, no lock
            except BaseException as e:
                box["error"] = e
            evt.set()
        elif work is not None:
            self._do_prefill(work)
        else:
            self._decode_iteration()
        return True

    def _chaos_pressure(self):
        """``page_pressure`` chaos: impound most of the KV free list for a
        bounded window of scheduler turns, forcing the preemption path."""
        frac = _chaos.page_pressure(self._loop_turn)
        if frac > 0.0:
            n = self.engine.allocator.impound(frac)
            self._pressure_until = self._loop_turn + 32
            _log("chaos page_pressure: impounded %d page(s) for 32 turns"
                 % n)
        elif self._pressure_until and self._loop_turn >= self._pressure_until:
            self._pressure_until = 0
            n = self.engine.allocator.release()
            _log("chaos page_pressure: released %d page(s)" % n)
            with self._cv:
                self._cv.notify_all()

    def _expire_locked(self, now):
        self._sweep_migration_locked(now)
        for i in range(len(self._pending) - 1, -1, -1):
            fut = self._pending[i].fut
            if now >= fut.deadline:
                del self._pending[i]
                self._reject_locked(fut, DeadlineExceeded(
                    "deadline passed while queued"))
        for s in list(self._active):
            if now >= s.fut.deadline:
                self._retire_locked(s, DeadlineExceeded(
                    "deadline passed after %d token(s)" % s.n_new))

    def _reject_locked(self, fut, err):
        if fut._reject(err):
            key = ("deadline_exceeded"
                   if isinstance(err, DeadlineExceeded) else
                   "shed_pages" if isinstance(err, Overloaded) else
                   "rejected_draining")
            self.stats[key] += 1
        self._cv.notify_all()

    def _retire_locked(self, seq, err=None):
        """Remove ``seq`` from the active batch, free its pages, settle.
        Idempotent: a sequence already retired (deadline expiry or drain
        sweep racing the decode loop) is left alone — pages free once."""
        if seq not in self._active:
            return
        self._active.remove(seq)
        pages = [int(p) for p in seq.table[:seq.n_pages]]
        if err is None:
            if seq.fut._resolve(list(seq.fut.stream_tokens)):
                self.stats["ok"] += 1
        else:
            self._reject_locked(seq.fut, err)
        if pages:
            self.engine.allocator.free(pages)
        self._cv.notify_all()

    # -- QoS preemption ------------------------------------------------
    def _preempt_locked(self, rank, need):
        """Free pages for a rank-``rank`` admission by preempting
        strictly-lower-priority active streams, lowest rank (then largest
        footprint) first.  Each victim is journaled as a patient
        :class:`_PendingReq` — its future stays live and it re-admits
        through the same resume path a gateway failover uses — so nothing
        is shed unless every victim is same-or-higher priority.  Returns
        True once ``need`` pages are free.  Caller holds the cv; the
        scheduler thread is the only decoder, so victims are never
        mid-device-step."""
        alloc = self.engine.allocator
        while alloc.capacity - alloc.used < need:
            victims = [s for s in self._active
                       if s.prio_rank < rank and not s.preempted
                       and not s.fut.done]
            if not victims:
                return False
            v = min(victims, key=lambda s: (s.prio_rank, -s.n_pages))
            self._preempt_seq_locked(v)
        return True

    def _preempt_seq_locked(self, seq):
        """Evict ``seq`` from the batch, journal its exact state (prompt +
        every generated token + its live sampling rng) and requeue it as a
        patient pending entry.  The future is NOT settled — the stream
        simply pauses until re-prefill."""
        self._active.remove(seq)
        seq.preempted = True
        tokens = np.concatenate(
            [seq.input_tokens, np.asarray(seq.gen_tokens, np.int32)])
        self._pending.append(_PendingReq(
            seq.fut, tokens, seq.max_new, seq.sampling, seq.prio_name,
            seq.prio_rank, start_new=seq.n_new, patient=True,
            tenant=seq.tenant))
        self.stats["preempted"] += 1
        _profiler.dispatch_count("gen_preempted")
        _telemetry.trace_instant(
            "gen.preempt", cat="gen",
            args={"priority": seq.prio_name, "tokens": seq.n_new})
        pages = [int(p) for p in seq.table[:seq.n_pages]]
        if pages:
            self.engine.allocator.free(pages)
        self._cv.notify_all()

    def _do_prefill(self, req):
        eng = self.engine
        fut, max_new, sampling = req.fut, req.max_new, req.sampling
        tokens = req.tokens
        need = -(-int(tokens.size) // eng.page_size)
        with _profiler.span("engine.gen.pages", phase="alloc"):
            pages = eng.allocator.alloc(need)
            if pages is None:
                with self._cv:
                    if self._preempt_locked(req.prio_rank, need):
                        pages = eng.allocator.alloc(need)
        if pages is None:
            if req.patient:
                # an internally-preempted stream waits out the pressure
                # instead of shedding; defer one turn to the decode side
                # so the batch keeps draining and freeing pages
                with self._cv:
                    self._inflight = None
                    self._pending.append(req)
                    self._defer_prefill = True
                    self._cv.notify_all()
                return
            _profiler.dispatch_count("gen_pages_shed")
            with self._cv:
                self._inflight = None
                self._reject_locked(fut, Overloaded(
                    "KV pages exhausted: prompt needs %d page(s), "
                    "%d free of %d" % (need, eng.allocator.capacity
                                       - eng.allocator.used,
                                       eng.allocator.capacity)))
            return
        table = np.zeros(eng.pages_per_seq, np.int32)
        table[:need] = pages
        with _profiler.span("engine.gen.prefill", tokens=int(tokens.size)):
            logits = eng.prefill(tokens, table)    # device work, no lock
        with _profiler.span("engine.gen.sample"):
            tok = _sample_token(logits, *sampling)
        with _profiler.span("engine.gen.settle"):
            self._settle_prefill(req, pages, table, need, tok)

    def _settle_prefill(self, req, pages, table, need, tok):
        eng = self.engine
        fut, max_new, sampling = req.fut, req.max_new, req.sampling
        tokens = req.tokens
        seq = _Seq(fut, table, need, int(tokens.size), tok, max_new,
                   int(tokens.size), sampling, prio_name=req.prio_name,
                   prio_rank=req.prio_rank, input_tokens=tokens,
                   start_new=req.start_new, tenant=req.tenant)
        is_eos = self.cfg.eos_id >= 0 and tok == self.cfg.eos_id
        emitted = False if is_eos else fut._emit(tok)  # EOS never streams
        if (emitted and req.start_new == 0
                and fut.t_first_token is not None):
            _telemetry.registry().histogram("gen.ttft_ms").observe(
                (fut.t_first_token - fut.t_admit) * 1e3)
        with self._cv:
            self._inflight = None
            if fut.done:                           # drain/deadline raced
                eng.allocator.free(pages)
            elif self.clock.now() >= fut.deadline:
                self._reject_locked(fut, DeadlineExceeded(
                    "deadline passed during prefill"))
                eng.allocator.free(pages)
            elif is_eos or seq.n_new >= max_new:
                self._active.append(seq)
                self._retire_locked(seq)
            else:
                self._active.append(seq)
                self._cv.notify_all()

    def _decode_iteration(self):
        eng = self.engine
        with self._cv:
            seqs = list(self._active)
        if not seqs:
            return
        with _profiler.span("engine.gen.pages", phase="grow"):
            survivors = self._grow_tables(seqs)
        if not survivors:
            return
        n = len(survivors)
        with _profiler.span("engine.gen.decode", active=n,
                            bucket=_pick_bucket(eng.slot_chain, n)) as sp:
            logits = eng.decode(survivors)         # device work, no lock
        dt = sp.end - sp.begin
        if dt > 0:
            _telemetry.registry().histogram(
                "gen.decode_tokens_per_sec").observe(n / dt)
        # sample, then advance + emit, with no lock held (token callbacks
        # are user code); settlement then happens under the lock, and
        # _retire_locked is idempotent against deadline/drain sweeps that
        # raced the step
        with _profiler.span("engine.gen.sample"):
            toks = [None if s.fut.done      # settled while decoding
                    else _sample_token(logits[i], *s.sampling)
                    for i, s in enumerate(survivors)]
        with _profiler.span("engine.gen.settle"):
            finished = []
            for s, tok in zip(survivors, toks):
                if tok is None:
                    finished.append(s)
                    continue
                s.length += 1
                if self.cfg.eos_id >= 0 and tok == self.cfg.eos_id:
                    finished.append(s)
                    continue
                s.last_token = tok
                s.gen_tokens.append(tok)
                s.n_new += 1
                if not s.fut._emit(tok):
                    finished.append(s)
                    continue
                if s.n_new >= s.max_new or s.length >= eng.max_seq:
                    finished.append(s)
            if finished:
                with self._cv:
                    for s in finished:
                        self._retire_locked(s)

    def _grow_tables(self, seqs):
        """Grow page tables for sequences crossing a page boundary; a pool
        miss first preempts strictly-lower-priority streams (journaled,
        not shed) and only sheds THIS sequence with a typed Overloaded
        when no lower-rank victim exists (its streamed tokens stand; the
        outcome names the truncation).  Returns the sequences that go to
        the device."""
        eng = self.engine
        survivors = []
        for s in seqs:
            if s.preempted or s.fut.done:
                continue
            needed = s.length // eng.page_size + 1
            if needed > s.n_pages:
                got = eng.allocator.alloc(1)
                if got is None:
                    with self._cv:
                        if self._preempt_locked(s.prio_rank, 1):
                            got = eng.allocator.alloc(1)
                if got is None:
                    _profiler.dispatch_count("gen_pages_shed")
                    with self._cv:
                        self._retire_locked(s, Overloaded(
                            "KV pages exhausted mid-decode after %d "
                            "token(s)" % s.n_new))
                    continue
                s.table[s.n_pages] = got[0]
                s.n_pages += 1
            survivors.append(s)
        # a grow-phase preemption may have evicted a sequence admitted to
        # survivors earlier in this same pass — its pages are gone, so it
        # must not reach the device; its journal already holds its state
        return [s for s in survivors if not s.preempted]

    # -- lifecycle -----------------------------------------------------
    def install_preemption_drain(self, handler=None):
        """Wire graceful drain into SIGTERM/SIGINT exactly like
        ``ModelServer.install_preemption_drain`` (rc-76 contract,
        docs/FAULT_TOLERANCE.md)."""
        from .elastic import install_preemption_drain

        handler = install_preemption_drain(self._drain_flag.set,
                                           handler=handler)
        self._preemption = handler
        return handler

    def drain(self, timeout=None):
        """Stop admission (typed :class:`Draining` rejections), let every
        admitted request reach its terminal outcome, then stop the
        scheduler.  On timeout, unresolved requests are swept with typed
        ``Draining`` so nothing ever hangs.  Returns True when everything
        in flight completed."""
        self._drain_flag.set()
        deadline = None if timeout is None else self.clock.now() + timeout
        with self._cv:
            if self._state == STOPPED:
                return True
            if self._state != DRAINING:
                self._state = DRAINING
                _log("state -> DRAINING (%d queued, %d active)"
                     % (len(self._pending), len(self._active)))
            self._cv.notify_all()
            while self._pending or self._active or self._limbo \
                    or self._inflight is not None:
                if deadline is not None and self.clock.now() >= deadline:
                    break
                self._cv.wait(0.05)
            drained = not (self._pending or self._active or self._limbo
                           or self._inflight is not None)
            if not drained:
                aborted = 0
                while self._pending:
                    fut = self._pending.popleft().fut
                    self._reject_locked(fut, Draining(
                        "drain timed out with the request still queued"))
                    aborted += 1
                if self._inflight is not None and not self._inflight.done:
                    self._reject_locked(self._inflight, Draining(
                        "drain timed out during prefill"))
                    aborted += 1
                for s in list(self._active):
                    if not s.fut.done:
                        self._retire_locked(s, Draining(
                            "drain timed out after %d token(s)" % s.n_new))
                        aborted += 1
                _log("drain timeout: aborted %d unresolved request(s) "
                     "with typed Draining" % aborted)
            # unexported parked / unclaimed imported streams die with the
            # server: their KV pages return to the pool so the leakcheck
            # ledger is quiescent at stop (an export racing this simply
            # finds the handle gone and the gateway re-prefills)
            self._sweep_migration_locked(float("inf"))
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=5.0)
        with self._cv:
            self._state = STOPPED
        return drained

    def close(self, timeout=5.0):
        return self.drain(timeout=timeout)

    def snapshot(self):
        with self._lock:
            alloc = self.engine.allocator
            return {
                "state": self._state,
                "pending": len(self._pending),
                "active": len(self._active),
                "parked": len(self._parked),
                "imports": len(self._imports),
                "pages_used": alloc.used,
                "pages_capacity": alloc.capacity,
                "kv_page_util_peak": round(alloc.peak_util, 4),
                "stats": dict(self.stats),
            }
