"""ServingEngine: continuous-batching inference over paged KV pools.

The engine owns the physical KV pools (one per CACHE layer of the
model's decode adapter: a K/V pair per weight layer, or per (pass,
layer) of a looped model, whose stack runs several times over a token
with keys and values of its own each time; each ``[n_kv, num_blocks,
block_size, head_dim]``, fp or int8 ``{"q8","s"}`` pages; a page id
names the same token span in every pool; a model with LATENT attention,
``decode_adapter().kv_layout == "latent"``, has ONE pool a cache layer
instead, ``[1, num_blocks, block_size, latent_pool_dim(latent_dim)]``:
``_kp`` carries those and ``_vp`` is an empty tuple, so that export,
hand-off and prefix import move them through the same codec, and the
step's attention and in-place write are the latent kernels of
``paged_attention.py``). Off the CPU the jitted steps
are DONATED the pools and return them: where the ragged kernel reads a
pool (``kv_write_impl``) the step's KV write is a Pallas call on the
kernel's own layout with the pools aliased through it (its operands:
the grid's bound, three rank-1 ``s32`` visit tables, the new K and V
rows, the K and V pool; no table of rank 2, so that
``benchmark/lib/xplane.py`` does not take it for the attention call),
so a step moves the tile groups it writes and no
other byte of a pool, and one copy of the pools is live. The arrays a
step was given are deleted by it: everything that touches a pool
(export, hand-off, prefix import, the host tier) runs under the
engine's lock between rounds and reads ``self._kp``/``self._vp`` afresh
(the results of the newest launched step: the device runs what is
enqueued in order, so a read waits for that step and a write lands
after it).
A model whose adapter says ``state_layers > 0`` (Nemotron-H's Mamba
mixers) carries, besides its pages, a RECURRENT STATE per sequence in each
of those layers, never paged: the engine allocates one entry a state layer
at ``[max_slots, ...]`` (``decode_adapter().state_shapes()``, a dict of
arrays each) and carries them in ``_kp`` AFTER the ``cache_layers`` paged
pools, so that the step's 13-argument call keeps its meaning, the
donation of its K and V arguments covers the state (written in place,
one copy live), and the benchmark's rehearsal lowers the real step. Row
r of a step is slot r: a row whose first token this step is at position
0 (a new request, a re-prefill after preemption, a slot's next occupant)
starts from zeros on the device, and no host pass zeroes anything. What
cannot carry that state is off or refused: such an engine registers and
matches no prefix (``stats().prefix_cache`` is False), and hand-off,
prefix export and import raise ``NotImplementedError`` naming the missing
mechanism, state snapshots.
The engine also owns a :class:`BlockManager` for the page index space, a
:class:`Scheduler` for slots, and exactly ONE jitted
program: a fixed-shape RAGGED step (``ragged_paged_attention``) whose
flat ``[token_budget]`` token axis packs every RUNNING slot's decode
token next to as many prefill-chunk tokens as fit, so mixed
prefill+decode traffic costs one dispatch per scheduler tick and
prefill no longer serializes against decode. Rows join and leave by
mask (``query_lens == 0`` = idle slot, position ``-1`` = padding), so
the step compiles once and never again (``ragged_compiles`` asserts
this).

The step program is pure — pools in, pools out — and an injected or
transport fault fires before the program is entered, with the pools
untouched, which makes the dispatch safely retryable (a program that
fails after it consumed its pools is a failed step like any other, and
ends the engine): the step body runs under
``resilience.call_with_retry`` (site ``serving.step``) with the retry
deadline derived from the nearest per-request deadline, and
``resilience.faults.check("serving.step")`` is consulted inside the
retried body so injected ``ConnectionError`` faults exercise the same
recovery path real transport errors would.

The step loop runs ONE STEP AHEAD of the tokens it reads: a round
(:meth:`ServingEngine.step`) launches step n+1 and only then waits for
step n's tokens and emits them, so the chip has its next program queued
while the host schedules, packs and transfers. What makes that possible
is that only the device needs a decode row's last token to run the next
step: the step takes the result of the step before and a mask of the
token positions that read their token from it. The scheduler counts a
request's token in flight (``Request.in_flight``) into positions, pages
and the tokens left to launch; an end the host cannot foresee (``eos``,
``cancel()``, a deadline) leaves one row in the step already launched,
which is dropped when that step is collected; slots and pages are
released at emission. What cannot be decided without the tokens collects
the step in flight first (``_drain``): a round that would preempt,
``take_handoff`` of a first token in flight, prefix export and import,
``fail_all`` and ``shutdown``. Whether a round overlaps depends only on
that state; there is no switch.

What the engine learns from the adapter and says on its spans
(telemetry on): the cache's layout and ``latent_dim``; of expert layers
``experts`` (the routed experts HELD by this model: the stacks its
grouped matmuls read), ``experts_routed`` (the router's width),
``experts_per_token``, ``moe_layers``, ``moe_rows``, ``moe_blocks`` (the
128-row blocks of ``moe_rows``) and ``moe_pairs`` (the pairs it computes
under even routing) on ``serving.ragged_step``. What only the step can
count it counts itself: the pairs it really dispatched to held experts
(a model that holds a SHARE of its routed experts, one chip of an
expert-parallel layer, computes only those) and the row blocks that
hold a pair (the grouped matmuls skip the others). The two counts ride
behind the rows' tokens in the step's result, so the one read a round
makes brings them, and go on ``serving.device_wait`` as
``moe_pairs_held`` beside ``moe_pairs_routed`` and ``moe_blocks_live``
beside ``moe_blocks``.

Requests stream tokens through per-request queues:
``rid = engine.submit(prompt)``, ``for tok in engine.stream(rid)``.
``engine.start()`` runs the step loop on a background thread;
tests may instead call ``engine.step()`` directly for determinism
(``while engine.step(): pass`` drains: the last rounds only collect).
"""
from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
import traceback
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..config import knobs as _knobs
from ..distributed.resilience import faults
from ..distributed.resilience.retry import call_with_retry, default_policy
from ..incubate.nn.pallas.paged_attention import (kv_write_impl,
                                                  latent_impl,
                                                  latent_pool_dim,
                                                  latent_visit_counts,
                                                  quantize_kv_pages,
                                                  ragged_impl,
                                                  ragged_visit_counts)
from ..models.generation import _sample
from ..observability import compile_ledger as _compile_ledger
from ..observability import scopes as _scopes
from ..observability.tracing import span
from .block_manager import BlockManager
from .kv_store import codec as kv_codec
from .scheduler import (CANCELLED, FINISHED, HANDOFF, PREFILL, RUNNING,
                        PrefillChunk, Request, Scheduler)

__all__ = ["ServingEngine", "RequestError", "EngineConfig",
           "RequestDescriptor", "EngineStats", "KVHandoff"]


@dataclasses.dataclass(frozen=True)
class RequestDescriptor:
    """Replayable snapshot of one in-flight request. Greedy decoding is
    deterministic, so ``prompt + generated`` resubmitted with
    ``remaining`` new tokens on ANY engine holding the same weights
    continues the exact same stream — this is what the cluster router
    replays after a replica death."""
    rid: int
    prompt: Tuple[int, ...]
    generated: Tuple[int, ...]
    remaining: int
    temperature: float
    top_p: float
    eos_id: Optional[int]
    deadline: Optional[float]          # absolute time.monotonic()
    state: str


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Lock-held health snapshot for routers/monitors (see
    :meth:`ServingEngine.stats`)."""
    free_blocks: int
    total_blocks: int
    watermark_blocks: int
    block_size: int
    queue_depth: int                   # waiting for a slot
    prefilling: int
    running: int
    active_slots: int
    max_slots: int
    ragged_compiles: int
    inflight: Tuple[RequestDescriptor, ...]
    # False where the model carries recurrent state, which a cached
    # prefix's pages cannot restore
    prefix_cache: bool = True

    def can_admit(self, n_blocks: int) -> bool:
        """Mirror of ``BlockManager.can_allocate`` over the snapshot."""
        return self.free_blocks - self.watermark_blocks >= n_blocks


@dataclasses.dataclass(frozen=True)
class KVHandoff:
    """One prefilled request leaving a prefill replica: prompt KV pages
    (native pool layout — fp arrays or int8 ``{"q8","s"}`` dicts, one
    per cache layer) plus everything a decode replica needs to seat it
    directly into a RUNNING slot."""
    src_rid: int                       # rid on the PREFILL engine
    prompt: Tuple[int, ...]
    first_token: int
    max_new_tokens: int
    temperature: float
    top_p: float
    eos_id: Optional[int]
    deadline: Optional[float]          # absolute time.monotonic()
    block_size: int
    kv_quant: Optional[str]
    num_blocks: int                    # pages carried per cache layer
    k_pages: Tuple[object, ...]        # per cache layer: [n_kv, nb, page, d]
    v_pages: Tuple[object, ...]

    def nbytes(self) -> int:
        return kv_codec.pages_nbytes(self.k_pages) + \
            kv_codec.pages_nbytes(self.v_pages)


@dataclasses.dataclass(frozen=True)
class _Flight:
    """A launched ragged step whose tokens the host has not read."""
    nxt: object                        # [max_slots] int32, on the device
    running: List[Request]             # its decode rows
    chunks: List[PrefillChunk]
    tokens: int = 0                    # live tokens it was packed with


class RequestError(RuntimeError):
    """A stream ended abnormally (cancelled / deadline / shutdown)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class EngineConfig:
    """Resolved engine knobs (ctor args win over env vars)."""

    def __init__(self, max_slots=None, block_size=None, num_blocks=None,
                 prefill_chunk=None, max_seq_len=None, kv_quant=None,
                 watermark=0.01, enable_prefix_cache=True, seed=0,
                 token_budget=None, name=None):
        # telemetry source label: access-log records and window
        # snapshots carry it (a Replica passes its replica name)
        self.name = str(name) if name else "engine"
        self.max_slots = max_slots or _knobs.get_int(
            "PADDLE_TPU_SERVE_SLOTS")
        self.block_size = block_size or _knobs.get_int(
            "PADDLE_TPU_SERVE_BLOCK_SIZE")
        self.num_blocks = num_blocks or _knobs.get_int(
            "PADDLE_TPU_SERVE_NUM_BLOCKS")
        self.prefill_chunk = prefill_chunk or _knobs.get_int(
            "PADDLE_TPU_SERVE_PREFILL_CHUNK")
        self.max_seq_len = max_seq_len
        self.kv_quant = kv_quant        # None | "int8"
        self.watermark = watermark
        self.enable_prefix_cache = enable_prefix_cache
        self.seed = seed
        # token axis of the ragged step: decode rows + prefill chunk
        # tokens packed per step (clamped to >= max_slots in the engine)
        self.token_budget = token_budget or _knobs.get_int(
            "PADDLE_TPU_SERVE_TOKEN_BUDGET",
            default=self.max_slots + self.prefill_chunk)
        if self.kv_quant not in (None, "int8"):
            raise ValueError("kv_quant must be None or 'int8'")
        if self.prefill_chunk <= 0:
            raise ValueError("prefill_chunk must be > 0")
        if self.token_budget <= 0:
            raise ValueError("token_budget must be > 0")


class _EngineLock:
    """The engine's lock: re-entrant, and no thread can keep it by
    asking again at once.

    The step loop holds it for a whole step and asks for it again a
    microsecond after letting go. Behind a bare ``RLock`` the callers
    that waited through the step are woken by the release and then lose
    the race to the loop's next ``acquire`` nearly every time: how long a
    ``submit()`` waited (on the v5e 1 s to 33 s, ``PERF.md`` PR 27) was
    the chance of a few microseconds. So a thread that does not hold the
    lock takes ``_gate`` first and keeps it until the lock is its own.
    What that guarantees: the holder of the gate is the next holder of
    the lock, and a loop that comes back while a caller waits finds the
    gate taken and waits at it like any caller. What it does not: among
    the threads waiting at the gate the order is the platform's
    (``threading.Lock`` wakes whom it likes), so this is not first in,
    first out.

    ``with lock:`` and ``with lock(site, rid):`` are the same thing while
    telemetry is off; while it is on, the second records the wait as a
    span ``serving.lock_wait`` (:class:`_LockWait`)."""

    __slots__ = ("_inner", "_gate", "_owner", "_depth")

    def __init__(self):
        self._inner = threading.Lock()
        self._gate = threading.Lock()
        self._owner: Optional[int] = None    # written by the holder only
        self._depth = 0

    def __enter__(self):
        me = threading.get_ident()
        if self._owner == me:                # re-entry: no turn to wait
            self._depth += 1
            return True
        with self._gate:
            self._inner.acquire()
        self._owner, self._depth = me, 1
        return True

    def __exit__(self, *exc) -> None:
        self._depth -= 1
        if not self._depth:
            self._owner = None
            self._inner.release()

    def __call__(self, site: str, rid: Optional[int] = None):
        if not _obs.enabled():
            return self
        return _LockWait(self, site, rid)


class _LockWait:
    """Telemetry on: take the engine's lock inside a span
    ``serving.lock_wait`` (``site``, and ``rid`` where the caller has
    one), which ends when the lock is held. ``waited`` is that span's
    duration in seconds."""

    __slots__ = ("_lock", "_args", "waited")

    def __init__(self, lock: _EngineLock, site: str, rid: Optional[int]):
        self._lock = lock
        self._args = {"site": site} if rid is None \
            else {"site": site, "rid": rid}
        self.waited = 0.0

    def __enter__(self) -> "_LockWait":
        with span("serving.lock_wait", args=self._args) as sp:
            self._lock.__enter__()
        self.waited = getattr(sp, "dur", 0.0) / 1e6
        return self

    def __exit__(self, *exc) -> None:
        self._lock.__exit__(*exc)


class _StepBody:
    """THE serving step's program, apart from the engine that runs it: it
    holds what tracing needs (the adapter, whose weights are detached, and
    whether the model has expert layers) and nothing of a weight or a
    pool, so that the compile ledger can keep a way back to the program
    (:meth:`ServingEngine.compiled_step`) without keeping the engine."""

    def __init__(self, ad, moe_layers: int):
        self._ad = ad
        self._moe_layers = moe_layers
        self.traces = 0
        # a re-trace by compiled_step()'s lowering is no compile of the
        # hot path
        self.quiet = False

    def lowering(self, jitted, args):
        """-> the zero-argument callable a ``SiteProgram`` wants: the hot
        path's own jit lowered for ``args`` (abstract). It closes over
        this body, never over the engine. jit keeps a trace by argument
        types, so after the first step this re-runs no Python; where it
        does re-trace, that is no compile of the hot path and is not
        counted (unless it is the first trace of all, which the hot path
        then reuses)."""
        def lower():
            self.quiet = self.traces > 0
            try:
                return jitted.lower(*args)
            finally:
                self.quiet = False

        return lower

    def _ragged_step(self, w, toks, pos, row_of, qs, ql, cl, kp, vp,
                     bt, temp, top_p, key, prev=None, from_prev=None):
        """One dispatch covers every decode row and every packed
        prefill-chunk token. Samples
        one candidate token per row from its last logit (idle rows
        sample garbage that the host discards). ``prev`` is the result
        of the step before (``[max_slots]``, never read back before it
        is used here) and ``from_prev`` says which token positions take
        their row's token from it: the host launches a step before it
        has read the last one's tokens. Without them (the 13-argument
        call) every token is the host's. Where the model has expert
        layers the result has two elements more, after the rows' tokens:
        the pairs the step dispatched to held experts, and the row
        blocks of its grouped matmuls that hold a pair."""
        if not self.quiet:
            self.traces += 1  # ptlint: disable=jit-purity  (trace-time compile counter)
            if _obs.enabled():
                _obs.registry.counter("serving.ragged_compiles").inc()
        if prev is not None:
            with _scopes.phase("carry"):
                toks = jnp.where(from_prev,
                                 jnp.take(prev, row_of, mode="clip"), toks)
        tally = {} if self._moe_layers else None
        lg, kp, vp = self._ad.ragged_chunk(
            w, toks, pos, row_of, qs, ql, cl, kp, vp, bt, tally)
        with _scopes.phase("head"):
            last = jnp.clip(qs + ql - 1, 0, toks.shape[0] - 1)
            lg = jnp.take(lg, last, axis=0)
        with _scopes.phase("sample"):
            nxt = _sample(lg, key, temp, top_p)
        if tally:
            with _scopes.phase("carry"):
                nxt = jnp.concatenate([nxt, jnp.stack(
                    [tally["moe_pairs_held"], tally["moe_blocks_live"]])])
        return nxt, kp, vp


class ServingEngine:
    def __init__(self, model, **knobs):
        cfg = EngineConfig(**knobs)
        self.config = cfg
        ad = model.decode_adapter()
        # detach the weights: the jitted steps take them as an argument,
        # so the adapter methods stay pure over arrays
        self._w, ad.weights = ad.weights, None
        self._ad = ad
        model_max = getattr(getattr(model, "config", None),
                            "max_position_embeddings", 2048)
        self.max_seq_len = min(cfg.max_seq_len or model_max, model_max)
        self.pages_per_seq = -(-self.max_seq_len // cfg.block_size)

        # Mamba-style layers: a state per slot beside the pages
        self._state_layers = getattr(ad, "state_layers", 0)
        self.manager = BlockManager(
            cfg.num_blocks, cfg.block_size, watermark=cfg.watermark,
            enable_prefix_cache=cfg.enable_prefix_cache
            and not self._state_layers)
        self.scheduler = Scheduler(self.manager, cfg.max_slots,
                                   self.max_seq_len)

        kvd = self._w["wte"].dtype
        latent = ad.kv_layout == "latent"
        if latent and cfg.kv_quant:
            raise ValueError("kv_quant=%r: a latent KV pool has no int8 "
                             "form" % (cfg.kv_quant,))
        shape = (ad.num_kv_heads, cfg.num_blocks, cfg.block_size,
                 latent_pool_dim(ad.latent_dim) if latent else ad.head_dim)
        if cfg.kv_quant == "int8":
            mk = lambda: quantize_kv_pages(jnp.zeros(shape, kvd))  # noqa: E731
        else:
            mk = lambda: jnp.zeros(shape, kvd)                     # noqa: E731
        # what the per-head kernel's plan reads of a pool
        self._pool_shape = shape
        self._pool_dtype = jnp.int8 if cfg.kv_quant == "int8" else kvd
        # a latent cache layer is ONE pool, carried where the K pools
        # are; the V side is then an empty tuple everywhere
        self._kp = tuple(mk() for _ in range(ad.cache_layers))
        self._vp = () if latent else \
            tuple(mk() for _ in range(ad.cache_layers))
        if self._state_layers:
            # after the pools, in the same tuple: donated, written in place
            self._kp += tuple(
                {k: jnp.zeros((cfg.max_slots,) + shape, dtype)
                 for k, (shape, dtype) in entry.items()}
                for entry in ad.state_shapes())
        # bytes of layer weights that one pass over the stack streams
        self._pass_weight_bytes = sum(
            a.nbytes for a in jax.tree_util.tree_leaves(self._w["layers"]))

        self._key = jax.random.PRNGKey(cfg.seed)
        # the step launched and not collected yet, and what a step is
        # given for "the step before" when there is none
        self._flight: Optional[_Flight] = None  # guarded by: _lock
        # expert layers: how many of a step's (token, expert) pairs went
        # to held experts, and how many row blocks of the grouped matmuls
        # hold a pair, only the step can count. It appends the two counts
        # to its tokens, so the one host read brings all three
        self._moe_layers = getattr(ad, "moe_layers", 0)
        self._no_tokens = jnp.zeros(
            cfg.max_slots + (2 if self._moe_layers else 0), jnp.int32)
        # off the CPU the step is donated its pools, so that the KV
        # write happens in place: no saved reference to a pool survives
        donate = jax.default_backend() != "cpu"
        self._body = _StepBody(ad, self._moe_layers)
        self._ragged_step = self._body._ragged_step
        self._ragged_fn = jax.jit(
            self._ragged_step, donate_argnums=(7, 8) if donate else ())
        self._donated_args = 2 if donate else 0     # kp and vp
        # which attention implementation the step program resolves to
        # ("pallas" | "xla"): a function of the backend and pool shapes
        # and which KV write: the in-place tile-group kernel or the scatter
        if latent:
            self.attention_impl = self.kv_write_impl = latent_impl(
                ad.latent_value_dim, cfg.block_size)
        else:
            self.attention_impl = ragged_impl(ad.head_dim, cfg.block_size)
            self.kv_write_impl = kv_write_impl(
                ad.head_dim, cfg.block_size, cfg.kv_quant == "int8")
        # the flat token axis must cover the worst-case decode rows
        # (max_slots - 1 running + 1 prefill slot needing >= 1 token)
        self._token_budget = max(cfg.token_budget, cfg.max_slots)
        # the ledger's way back to this step's program (compiled_step):
        # the jit above, and the shapes and shardings _launch calls it with
        T, R = self._token_budget, cfg.max_slots
        tok, row = (jax.ShapeDtypeStruct((n,), jnp.int32) for n in (T, R))
        per_row = jax.ShapeDtypeStruct((R,), jnp.float32)
        args = _compile_ledger.abstract_args((
            self._w, tok, tok, tok, row, row, row, self._kp, self._vp,
            jax.ShapeDtypeStruct((R, self.pages_per_seq), jnp.int32),
            per_row, per_row, self._key, self._no_tokens,
            jax.ShapeDtypeStruct((T,), jnp.bool_)))
        self._program = _compile_ledger.register_program(
            "serving.ragged_step", _compile_ledger.SiteProgram(
                self._body.lowering(self._ragged_fn, args)))
        # what the step's span says of the model beside its passes
        self._step_attrs = {"kv_layout": ad.kv_layout}
        if latent:
            self._step_attrs["latent_dim"] = ad.latent_dim
        if self._moe_layers:
            from ..incubate.nn.pallas.moe_dispatch import (_BM,
                                                           dispatch_rows)
            rows = self._moe_layers * dispatch_rows(
                self._token_budget, ad.experts_per_token, ad.experts)
            self._step_attrs.update(
                experts=ad.experts, experts_routed=ad.experts_routed,
                experts_per_token=ad.experts_per_token,
                moe_layers=self._moe_layers, moe_rows=rows,
                moe_blocks=rows // _BM)
        if getattr(ad, "hc_streams", 1) > 1:
            self._step_attrs["hc_streams"] = ad.hc_streams
        if self._state_layers:
            c = ad.cfg
            self._step_attrs.update(
                state_layers=self._state_layers,
                ssm_heads=c.mamba_num_heads, ssm_head_dim=c.mamba_head_dim,
                ssm_state_size=c.ssm_state_size, ssm_groups=c.n_groups,
                state_bytes_row=ad.state_bytes())

        self._lock = _EngineLock()
        self._wakeup = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._requests: Dict[int, Request] = {}  # guarded by: _lock
        self._streams: Dict[int, "queue.Queue"] = {}  # guarded by: _lock
        self._handoff_ready: List[Request] = []  # guarded by: _lock
        self._dead = False  # guarded by: _lock (fail_all called)
        # cluster KV tier hooks (set_kv_hooks): registration/eviction
        # of prefix-cached blocks flows to the cluster store when wired
        self._kv_register = None  # guarded by: _lock
        self._kv_evict = None  # guarded by: _lock
        self.manager.set_hooks(on_evict=self._on_block_evicted,
                               on_register=self._on_block_registered)
        # request-scoped observability (PR 16): access log + rolling
        # windows + SLO engine, all built lazily on first touch so a
        # telemetry-disabled engine allocates none of it
        self._log = None
        self._slo = None

    # ------------------------------------------------ the step's program
    @property
    def ragged_compiles(self) -> int:
        """Times the hot path traced (and so compiled) its step: 1."""
        return self._body.traces

    def compiled_step(self):
        """The ``jax.stages.Compiled`` of exactly the jit ``step()``
        calls: same function object, same donation, this engine's shapes
        and shardings, so jit hands back the executable the engine runs
        (in a fresh process: a load from the persistent compile cache) and
        not a second program. Lowered on the first call and kept (``compile_ledger.SiteProgram``); it counts as
        no compile of the hot path (``ragged_compiles``, the ledger's
        ``compiles``). The compile ledger keeps the same program for site
        ``serving.ragged_step`` until another engine registers there, for
        ``observability.op_phases`` to read after this engine is gone:
        what that pins is the step's body (the adapter without its
        weights) and abstract arguments, never a weight or a pool."""
        return self._program.compiled()

    # --------------------------------------------- request observability
    @property
    def request_log(self):
        """This engine's access log (+ rolling ``rt.*`` windows).
        Created on first access; records accumulate only while
        telemetry is enabled."""
        if self._log is None:
            from ..observability.request_log import RequestLog
            self._log = RequestLog(source=self.config.name)
        return self._log

    @property
    def windows(self):
        """Rolling-window instruments (``rt.*``) for this engine."""
        return self.request_log.windows

    @property
    def slo(self):
        """SLO engine over this engine's rolling windows."""
        if self._slo is None:
            from ..observability.slo import SLOEngine
            self._slo = SLOEngine(self.windows)
        return self._slo

    def ops_snapshot(self) -> dict:
        """One JSON-able dict with everything the ops dashboard
        renders: per-source window snapshots, the SLO report, the
        autoscaler signal feed, latency attribution, and the
        access-log tail. ``tools/ptop.py --snapshot`` reads this shape
        (the router emits the same shape with more replicas)."""
        st = self.stats()
        log = self.request_log
        return {
            "kind": "ops_snapshot", "source": self.config.name,
            "ts": time.time(),
            "replicas": {self.config.name: {
                "alive": not self.dead,
                "queue_depth": st.queue_depth,
                "active_slots": st.active_slots,
                "max_slots": st.max_slots,
                "running": st.running, "prefilling": st.prefilling,
                "free_blocks": st.free_blocks,
                "total_blocks": st.total_blocks,
                "windows": log.windows.snapshot()}},
            "slo": self.slo.evaluate(),
            "signals": self.slo.load_signals(),
            "attribution": log.attribution(),
            "requests": log.tail(50)}

    def dump_ops_snapshot(self, path: str) -> dict:
        snap = self.ops_snapshot()
        from ..observability.request_log import write_snapshot
        write_snapshot(snap, path)
        return snap

    # ----------------------------------------------------- public intake
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               temperature: float = 0.0, top_p: float = 1.0,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               handoff: bool = False) -> int:
        """Queue a request; returns its rid for stream()/cancel().
        ``handoff=True`` (disaggregated prefill) stops after the prompt
        is prefilled and the first token sampled — the request then
        waits in the handoff queue for :meth:`take_handoff` instead of
        decoding here.

        The access log's clock starts under the engine's lock, so the
        wait for that lock is not in the record's ``e2e_s``: it is the
        record's ``lock_wait_s`` (and the span ``serving.lock_wait``,
        site ``submit``)."""
        prompt = [int(t) for t in prompt]
        if handoff:
            self._no_state_snapshots("submit(handoff=True)")
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                "prompt %d + max_new_tokens %d exceeds max_seq_len %d"
                % (len(prompt), max_new_tokens, self.max_seq_len))
        now = time.monotonic()
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      temperature=float(temperature), top_p=float(top_p),
                      eos_id=eos_id, arrival=now,
                      deadline=None if deadline_s is None
                      else now + deadline_s,
                      handoff=bool(handoff))
        with self._lock("submit", req.rid) as held:
            if self._dead:
                raise RequestError("replica_dead")
            if _obs.enabled():
                req.timeline = self.request_log.open(
                    req.rid, prompt_tokens=len(prompt),
                    lock_wait_s=getattr(held, "waited", 0.0))
            self._requests[req.rid] = req
            self._streams[req.rid] = queue.Queue()
            self.scheduler.add(req)
        self._wakeup.set()
        return req.rid

    def stream(self, rid: int) -> Iterator[int]:
        """Per-token iterator; raises RequestError on abnormal end."""
        with self._lock("stream", rid):
            q = self._streams[rid]
        while True:
            kind, val = q.get()
            if kind == "tok":
                yield val
            elif val in ("eos", "length"):
                return
            else:
                raise RequestError(val)

    def cancel(self, rid: int, reason: str = "cancelled") -> None:
        with self._lock("cancel", rid):
            req = self._requests.get(rid)
            if req is None:
                return
            self.scheduler.cancel(req, reason)
            self._end_stream(req, reason)

    def result(self, rid: int) -> List[int]:
        """Convenience: drain the whole stream into a list."""
        return list(self.stream(rid))

    def events(self, rid: int) -> Iterator[Tuple[str, object]]:
        """Raw per-request event iterator: ``("tok", t)`` items followed
        by one ``("end", reason)``. Unlike :meth:`stream` this exposes
        the termination reason, which the cluster router needs to tell
        a normal end (eos/length) from a replica death it must replay."""
        with self._lock("events", rid):
            q = self._streams[rid]
        while True:
            kind, val = q.get()
            yield kind, val
            if kind != "tok":
                return

    # ----------------------------------------------------- health/stats
    def _slot_counts(self) -> Tuple[int, int]:  # ptlint: holds=_lock
        """-> (running, prefilling) over the occupied slots; a parked
        hand-off counts as prefilling."""
        prefilling = running = 0
        for r in self.scheduler.slots.values():
            if r.state == RUNNING:
                running += 1
            elif r.state in (PREFILL, HANDOFF):
                prefilling += 1
        return running, prefilling

    def _descriptor(self, req: Request) -> RequestDescriptor:  # ptlint: holds=_lock
        return RequestDescriptor(
            rid=req.rid, prompt=tuple(req.prompt),
            generated=tuple(req.generated), remaining=req.remaining,
            temperature=req.temperature, top_p=req.top_p,
            eos_id=req.eos_id, deadline=req.deadline, state=req.state)

    def stats(self) -> EngineStats:
        """Thread-safe health snapshot: free/watermark blocks, slot and
        queue occupancy, and replayable descriptors of every in-flight
        request. The whole snapshot is built under ``_lock`` (the fields
        read here are `# guarded by: _lock` / caller-guarded state) so
        it is internally consistent — a router sees matching queue depth
        and descriptor list, never a torn read."""
        with self._lock("stats"):
            running, prefilling = self._slot_counts()
            inflight = tuple(
                self._descriptor(r) for r in self._requests.values()
                if r.state not in (FINISHED, CANCELLED))
            return EngineStats(
                free_blocks=self.manager.num_free(),
                total_blocks=self.manager.num_blocks,
                watermark_blocks=self.manager.watermark_blocks,
                block_size=self.manager.block_size,
                queue_depth=len(self.scheduler.waiting),
                prefilling=prefilling,
                running=running,
                active_slots=self.scheduler.num_active(),
                max_slots=self.config.max_slots,
                ragged_compiles=self.ragged_compiles,
                inflight=inflight,
                prefix_cache=self.manager.enable_prefix_cache)

    @property
    def dead(self) -> bool:
        with self._lock:
            return self._dead

    def fail_all(self, reason: str = "replica_dead") \
            -> Tuple[RequestDescriptor, ...]:
        """Simulated replica crash: atomically capture a replayable
        descriptor for every live request, cancel them all (streams end
        with ``reason``), release every page, and refuse further work.
        The returned descriptors are the router's drain list."""
        with self._lock:
            self._settle()
            self._dead = True
            descs = []
            for req in list(self._requests.values()):
                if req.state in (FINISHED, CANCELLED):
                    continue
                descs.append(self._descriptor(req))
                self.scheduler.cancel(req, reason)
                self._end_stream(req, reason)
            self._handoff_ready.clear()
            return tuple(descs)

    # ------------------------------------------------------- AOT warmup
    def warmup(self, token: int = 0) -> None:
        """AOT warmup: run one tiny request through the engine so the
        step program is traced and compiled before real traffic
        arrives, and a fresh replica serves its first token without a
        cold compile. The 1-token prompt registers nothing in the prefix
        cache (only full blocks are hashed) and the pool drains back to
        empty.

        The warmup request is synthetic, so it records into a scratch
        access log that is discarded afterwards: its compile-inflated
        TTFT must not land in the real ``rt.*`` windows, where one
        multi-second sample would keep the SLO burn (and with it the
        autoscaler's ``want_scale_up`` hint) lit for the whole slow
        horizon."""
        if self._thread is not None:
            raise RuntimeError("warmup() must run before start()")
        from ..observability.request_log import RequestLog
        real_log = self._log
        self._log = RequestLog(source=self.config.name + ".warmup")
        try:
            rid = self.submit([int(token)], max_new_tokens=2)
            steps = 0
            while self.step():
                steps += 1
                if steps > 64:
                    raise RuntimeError("warmup failed to drain")
            list(self.stream(rid))      # queue already holds the end
            with self._lock:
                self._requests.pop(rid, None)
                self._streams.pop(rid, None)
        finally:
            self._log = real_log

    # ------------------------------------------- disaggregated handoff
    def _no_state_snapshots(self, what: str) -> None:
        """Moving a request or a prefix between engines moves its pages;
        a model with recurrent state would need its state moved too."""
        if self._state_layers:
            raise NotImplementedError(
                "%s: this model carries a recurrent state per sequence in "
                "%d layers beside its KV pages, and the engine takes no "
                "snapshot of it (state snapshots are not implemented), so "
                "a request or a prefix cannot leave or join it by its "
                "pages" % (what, self._state_layers))

    def _export_pages(self, blocks: List[int]):  # ptlint: holds=_lock
        """Materialize the KV pages of ``blocks`` (host copies, native
        pool layout) through the shared :mod:`kv_store.codec`."""
        return (kv_codec.take_pages(self._kp, blocks),
                kv_codec.take_pages(self._vp, blocks))

    @staticmethod
    def _import_pages(pool, blocks, pages):
        """Write exported pages into this engine's pool at ``blocks``
        (shared :mod:`kv_store.codec` — the one int8<->fp decode rule)."""
        return kv_codec.put_pages(pool, blocks, pages)

    def take_handoff(self) -> Optional[KVHandoff]:
        """Pop one prefilled request off the handoff queue as a
        :class:`KVHandoff` payload; its pages are exported (host
        copies) and then released here — full prompt blocks go to the
        prefix cache exactly like a normal completion, so repeated
        prefixes still hit on this prefill replica."""
        self._no_state_snapshots("take_handoff")
        with self._lock:
            flight = self._flight
            if flight is not None and any(
                    ch.last and ch.req.handoff for ch in flight.chunks):
                # a hand-off's first token is in flight: read it. (One
                # already parked needs no wait: every step that wrote
                # its pages was collected before it parked.)
                self._drain("handoff")
            while self._handoff_ready:
                req = self._handoff_ready.pop(0)
                if req.state != HANDOFF:
                    continue            # cancelled while parked
                k, v = self._export_pages(req.blocks)
                payload = KVHandoff(
                    src_rid=req.rid,
                    prompt=tuple(req.prompt),
                    first_token=int(req.handoff_token),
                    max_new_tokens=req.max_new_tokens,
                    temperature=req.temperature, top_p=req.top_p,
                    eos_id=req.eos_id, deadline=req.deadline,
                    block_size=self.manager.block_size,
                    kv_quant=self.config.kv_quant,
                    num_blocks=len(req.blocks), k_pages=k, v_pages=v)
                self.scheduler.finish(req, "handoff")
                self._end_stream(req, "handoff")
                return payload
            return None

    def adopt_handoff(self, payload: KVHandoff) -> Optional[int]:
        """Seat a :class:`KVHandoff` from a prefill replica straight
        into a RUNNING decode slot: allocate pages, import the KV, and
        decode from position ``len(prompt)`` on. Returns the local rid,
        or ``None`` when this engine has no free slot / pages right now
        (the caller re-offers later). The first token was already
        sampled by the prefill replica and is NOT re-emitted here."""
        self._no_state_snapshots("adopt_handoff")
        if payload.block_size != self.manager.block_size:
            raise ValueError(
                "handoff block_size %d != engine block_size %d"
                % (payload.block_size, self.manager.block_size))
        with self._lock:
            if self._dead:
                return None
            need = payload.num_blocks
            if not self.scheduler._free_slots or \
                    not self.manager.can_allocate(need):
                return None
            blocks = self.manager.allocate(need)
            self._kp = tuple(
                self._import_pages(p, blocks, pg)
                for p, pg in zip(self._kp, payload.k_pages))
            self._vp = tuple(
                self._import_pages(p, blocks, pg)
                for p, pg in zip(self._vp, payload.v_pages))
            req = Request(prompt=list(payload.prompt),
                          max_new_tokens=payload.max_new_tokens,
                          temperature=payload.temperature,
                          top_p=payload.top_p, eos_id=payload.eos_id,
                          deadline=payload.deadline,
                          arrival=time.monotonic())
            req.generated = [payload.first_token]
            req.remaining = payload.max_new_tokens - 1
            req.first_token_at = req.arrival
            if _obs.enabled():
                # adopted requests skip queue/prefill here; TTFT is NOT
                # stamped — the first token streamed on the prefill
                # replica, a local ~0 would corrupt the window
                tl = self.request_log.open(
                    req.rid, prompt_tokens=len(req.prompt))
                tl.mark_admitted()
                tl.mark_running(stamp_ttft=False)
                req.timeline = tl
            self.scheduler.place_running(req, blocks)
            self._requests[req.rid] = req
            self._streams[req.rid] = queue.Queue()
        self._wakeup.set()
        return req.rid

    # ------------------------------------------------ cluster KV tier
    def set_kv_hooks(self, on_register=None, on_evict=None) -> None:
        """Wire this engine into a cluster KV store.  ``on_register(h)``
        fires when a prefix block is published under chain hash ``h``;
        ``on_evict(h, k_pages, v_pages)`` fires when a cached block is
        about to be reused, with its pages already exported (host
        copies) so the tier can spill instead of discard.  Both run
        under the engine lock — hooks must not call back into the
        engine (enqueue and return)."""
        with self._lock:
            self._kv_register = on_register
            self._kv_evict = on_evict

    def _on_block_registered(self, bid: int, h: int) -> None:  # ptlint: holds=_lock
        # BlockManager hook; runs under _lock (manager is only mutated
        # under it), may re-enter via the RLock
        cb = self._kv_register
        if cb is not None:
            cb(h)

    def _on_block_evicted(self, bid: int, h: int) -> None:  # ptlint: holds=_lock
        # fires BEFORE the page is reused/forgotten: the one moment the
        # block's KV can still be saved. Export is a single-block host
        # copy — synchronous by necessity (the page is overwritten the
        # instant this returns); quantize/spill happen on the pump.
        cb = self._kv_evict
        if cb is None:
            return
        k, v = self._export_pages([bid])
        cb(h, k, v)

    def probe_prefix(self, prompt: Sequence[int]) -> int:
        """Local prefix-cache depth (whole blocks) without taking refs."""
        with self._lock:
            return self.manager.probe_prefix(list(prompt))

    def export_prefix(self, prompt: Sequence[int]):
        """Export the pages of this engine's longest cached prefix of
        ``prompt`` (host copies, native pool layout).  Returns
        ``(k_pages, v_pages, n_blocks)`` or None when nothing matches.
        The blocks are revived+freed around the copy, so they stay
        MRU in the evictable cache — serving a cross-replica fetch
        refreshes the prefix here too."""
        self._no_state_snapshots("export_prefix")
        with self._lock:
            if self._dead:
                return None
            blocks, _ = self.manager.match_prefix(list(prompt))
            if not blocks:
                return None
            # the copy waits for the step in flight anyway: read its
            # tokens while here
            self._drain("export")
            k, v = self._export_pages(blocks)
            self.manager.free(blocks)
            return k, v, len(blocks)

    def import_prefix(self, prompt: Sequence[int], n_blocks: int,
                      k_pages, v_pages) -> int:
        """Seat a fetched prefix into this engine's prefix cache:
        allocate pages, import the KV through the shared codec, publish
        the blocks under the prompt's chain hashes, and park them in
        the evictable LRU — the scheduler's normal ``match_prefix``
        then hits them at admission.  Returns tokens made resident (0
        when the local cache is already at least as deep, the pool
        can't take the pages, or prefix caching is off).  Raises
        ``ValueError`` for fp pages offered to an int8 pool (the codec
        refuses lossy requantization)."""
        self._no_state_snapshots("import_prefix")
        bs = self.manager.block_size
        with self._lock:
            if self._dead or not self.manager.enable_prefix_cache:
                return 0
            n = min(int(n_blocks), (len(prompt) - 1) // bs)
            if n <= 0 or self.manager.probe_prefix(prompt) >= n:
                return 0
            if not self.manager.can_allocate(n):
                return 0
            self._drain("export")

            def clip(pg):
                if n == n_blocks:
                    return pg
                if isinstance(pg, dict):
                    return {"q8": pg["q8"][:, :n], "s": pg["s"][:, :n]}
                return pg[:, :n]

            blocks = self.manager.allocate(n)
            self._kp = tuple(
                kv_codec.put_pages(p, blocks, clip(pg))
                for p, pg in zip(self._kp, k_pages))
            self._vp = tuple(
                kv_codec.put_pages(p, blocks, clip(pg))
                for p, pg in zip(self._vp, v_pages))
            # first-writer-wins: blocks whose chain hash is already
            # cached here stay unregistered and fall back to the free
            # list on free() — no leak, no double-mapping
            self.manager.register_prefix(list(prompt)[:n * bs], blocks)
            self.manager.free(blocks)
            return n * bs

    def demote_evictable(self, n: int) -> int:
        """Watermark-driven proactive demotion: when the free list has
        drained to the admission watermark, hand up to ``n`` LRU
        evictable blocks to the KV tier (via the eviction hook) and
        return them to the free list.  No-op while free blocks are
        plentiful or no tier is wired."""
        with self._lock:
            if self._dead or self._kv_evict is None:
                return 0
            # pressure signal: the DIRECTLY usable free list (not
            # counting evictable pages) is at/below the watermark
            if self.manager.free_list_size() > \
                    self.manager.watermark_blocks:
                return 0
            return len(self.manager.pop_evictable(n))

    # ------------------------------------------------------- step engine
    def step(self) -> bool:
        """One scheduler round, in two halves: LAUNCH the next ragged
        step (admit, pack every decode row and the prefill chunks that
        fit, transfer, enqueue), then COLLECT the step launched the
        round before (wait for its tokens, emit them). The step just
        launched stays in flight, so the chip has its next program
        queued while the host works and a round costs
        ``max(device, host)``; a decode row whose last token is still in
        flight is packed with a placeholder and takes the token from the
        step before on the device. A round that would preempt collects
        first (:meth:`_drain`) and then launches as a serial engine
        would. Returns False only when nothing is in flight and there
        was nothing to launch, so ``while eng.step()`` drains.

        Telemetry on, the round is one span ``serving.step`` (the wait
        for the lock is ``serving.lock_wait`` before it) which carries
        at its end what the scheduler and the block manager hold; its
        children, in order and with nothing between them:
        ``serving.schedule``, ``.build_batch``, ``.transfer``,
        ``.ragged_step`` (the enqueue of the step being launched),
        ``.device_wait``, ``.emit`` (of the step being collected)."""
        with self._lock("step"), span("serving.step") as st:
            if self._dead:
                return False
            older = self._flight
            admitted, plan = self._schedule(older is not None)
            if plan is None:
                # the pool is dry and a victim's last token may still be
                # on the device: preemption folds the tokens a request
                # has generated into its prompt, so read them first
                self._drain("preempt")
                older = None
                more, plan = self._schedule(False)
                admitted += more
            preempted, running, chunks = plan
            tokens = 0
            if running or chunks:
                # a failed launch leaves the older step in flight
                self._flight, tokens = self._launch(running, chunks, older)
            else:
                self._flight = None
            if older is not None:
                self._collect(older)
            if _obs.enabled():
                self._observe_step(st, preempted, tokens)
            return bool(admitted or tokens or older is not None)

    def _admit(self) -> List[Request]:  # ptlint: holds=_lock
        admitted = self.scheduler.admit()
        for req in admitted:
            if req.num_cached and _obs.enabled():
                _obs.registry.counter(
                    "serving.prefix_hit_tokens").inc(req.num_cached)
                if req.timeline is not None:
                    req.timeline.mark_prefix_hit(req.num_cached)
        return admitted

    def _observe_step(self, st, preempted, tokens) -> None:  # ptlint: holds=_lock
        """Telemetry on only: the step's end state, read from what the
        scheduler and the block manager already hold, onto the step's
        span and into the rolling ``rt.*`` gauges."""
        if preempted:
            _obs.registry.counter("serving.preemptions").inc(
                len(preempted))
        running, prefilling = self._slot_counts()
        waiting = len(self.scheduler.waiting)
        slots = self.config.max_slots
        pages = self.manager.num_blocks
        for k, v in (("running", running), ("prefilling", prefilling),
                     ("waiting", waiting), ("slots_max", slots),
                     ("pages_in_use", pages - self.manager.num_free()),
                     ("pages_max", pages), ("tokens", tokens)):
            st.set_arg(k, v)
        win = self.request_log.windows
        win.gauge("rt.queue_depth").set(waiting)
        win.gauge("rt.slot_util").set(
            self.scheduler.num_active() / slots)

    def _model_step_attrs(self, qs, ql, cl, tokens) -> dict:
        """Telemetry on only: what ``serving.ragged_step`` says of the
        model's mechanisms, from the host's own arrays: the cache's
        layout and, for a latent cache, the (query, key) pairs the step's
        attention scores in one cache layer (query token j of a row of n
        sees ``context - n + j + 1`` keys) and the latent kernel's work
        a cache layer by its own rule (``latent_visit_counts``): its
        (q block, row, page) visits, ``latent_page_visits``, and its grid
        steps, each a group of pages, ``latent_visits``; for a per-head
        cache that the kernel reads (``attention_impl`` ``"pallas"``;
        the XLA composition has no visits to count) the per-head
        kernel's work a cache layer by its own rule
        (``ragged_visit_counts``): its live (row, page) pairs,
        ``ragged_page_visits``, and its (row, group of pages) visits of
        a head block, ``ragged_visits``; for expert
        layers the (token, expert) pairs this model computes under even
        routing (every routed pair where every routed expert is held,
        else the held experts' share of them: what the step really
        dispatched comes back with its tokens, ``_collect``) and the rows
        the grouped matmuls run over (static: every pair and a row block
        of slack a held expert); for state layers the rows whose state
        the step reads and writes (``state_rows``), those that restart
        from zeros (``state_resets``) and the live tokens the scan takes
        (``ssm_tokens``), beside the static ``state_layers``,
        ``ssm_heads``, ``ssm_head_dim``, ``ssm_state_size``,
        ``ssm_groups`` and ``state_bytes_row`` (one row's state over all
        the state layers)."""
        attrs = dict(self._step_attrs)
        if self._ad.kv_layout == "latent":
            n, c = ql.astype(np.int64), cl.astype(np.int64)
            attrs["attn_pairs"] = int(np.sum(n * (c - n) + n * (n + 1) // 2))
            attrs["latent_page_visits"], attrs["latent_visits"] = \
                latent_visit_counts(self._token_budget,
                                    self.config.block_size,
                                    self.pages_per_seq, cl, ql, qs)
        elif self.attention_impl == "pallas":
            attrs["ragged_page_visits"], attrs["ragged_visits"] = \
                ragged_visit_counts(self._token_budget, self._ad.num_heads,
                                    self._pool_shape, self._pool_dtype,
                                    self._w["wte"].dtype,
                                    self.pages_per_seq, cl, ql)
        if self._moe_layers:
            attrs["moe_pairs"] = round(
                self._pairs_routed(tokens) * self._ad.experts
                / self._ad.experts_routed)
        if self._state_layers:
            # rows whose state the step reads and writes, those of them
            # that start from zeros (first token at position 0), tokens
            attrs["state_rows"] = int(np.count_nonzero(ql))
            attrs["state_resets"] = int(np.count_nonzero((ql > 0)
                                                         & (cl == ql)))
            attrs["ssm_tokens"] = int(tokens)
        return attrs

    def _pairs_routed(self, tokens) -> int:
        """(token, chosen expert) pairs of ``tokens`` live tokens, over
        the expert layers, wherever their experts are."""
        return int(tokens) * self._moe_layers * self._ad.experts_per_token

    def _dispatch(self, fn):  # ptlint: holds=_lock
        """Run one jitted step under the resilience machinery: injected
        or real ConnectionError/TimeoutError gets retried with backoff,
        bounded by the nearest per-request deadline."""
        nearest = None
        now = time.monotonic()
        for req in self.scheduler.slots.values():
            if req.deadline is not None:
                left = max(0.0, req.deadline - now)
                nearest = left if nearest is None else min(nearest, left)

        def body():
            act = faults.check("serving.step")
            if act is not None:
                faults.apply(act)
            return fn()

        return call_with_retry(body, default_policy(deadline=nearest),
                               site="serving.step")

    def _schedule(self, flying: bool):  # ptlint: holds=_lock
        """The round's first phase: expire deadlines, admit, secure the
        page of every decode row's next write, pick the rows and pack
        the prefill chunks of the step to launch.
        -> (admitted, (preempted, decode rows, chunks)); the plan is
        None where a step is in flight (``flying``) and the pool is too
        dry to go on without preempting: the caller collects that step
        and asks again."""
        T = self._token_budget
        with span("serving.schedule") as sp:
            self._expire_deadlines()
            admitted = self._admit()
            if flying and self.scheduler.decode_pages_short():
                return admitted, None
            preempted = self.scheduler.ensure_decode_blocks()
            running = self.scheduler.decode_rows()
            chunks = self.scheduler.next_prefills(T - len(running))
            if _obs.enabled():
                sp.set_arg("admitted", len(admitted))
                sp.set_arg("preempted", len(preempted))
        return admitted, (preempted, running, chunks)

    def _launch(self, running, chunks, older):  # ptlint: holds=_lock
        """Build, transfer and enqueue ONE ragged mixed batch: every
        decode row contributes its last token, then PREFILL slots pack
        prompt chunks into the remaining token budget (oldest first).
        All arrays are fixed padded shapes — [token_budget] tokens,
        [max_slots] rows (row index == slot index) — so the single jit
        traces exactly once for the engine's lifetime. A row whose last
        token is in flight (in ``older``, the step launched the round
        before and not collected yet) is packed with a placeholder and
        ``from_prev`` set: the step reads that token from ``older``'s
        result on the device, which the host never waits for here.

        Once the step is enqueued the requests are advanced to what it
        will have done: a chunk's tokens count as prefilled (the device
        runs the steps in order, so their KV is resident before a later
        step reads it), a row's token as in flight, and a prompt whose
        last chunk went out decodes from the next launch on.
        -> (the step in flight, tokens packed).

        Spans, in order: ``serving.build_batch``, ``.transfer``,
        ``.ragged_step`` (the enqueue)."""
        cfg = self.config
        R = cfg.max_slots
        T = self._token_budget
        on = _obs.enabled()
        with span("serving.build_batch"):
            toks = np.zeros(T, np.int32)
            from_prev = np.zeros(T, np.bool_)
            pos = np.full(T, -1, np.int32)
            row_of = np.full(T, -1, np.int32)
            qs = np.zeros(R, np.int32)
            ql = np.zeros(R, np.int32)
            cl = np.zeros(R, np.int32)
            temp = np.zeros(R, np.float32)
            top_p = np.ones(R, np.float32)
            bt = np.zeros((R, self.pages_per_seq), np.int32)
            cursor = 0
            for req in running:
                s = req.slot
                qs[s] = cursor
                ql[s] = 1
                cl[s] = n = req.total_len()
                if req.in_flight:
                    from_prev[cursor] = True
                else:
                    toks[cursor] = req.generated[-1]
                pos[cursor] = n - 1          # req.decode_pos()
                row_of[cursor] = s
                temp[s] = req.temperature
                top_p[s] = req.top_p
                bt[s, :len(req.blocks)] = req.blocks
                cursor += 1
            for ch in chunks:
                req = ch.req
                s = req.slot
                n = len(ch.tokens)
                qs[s] = cursor
                ql[s] = n
                cl[s] = ch.start + n
                toks[cursor:cursor + n] = ch.tokens
                pos[cursor:cursor + n] = np.arange(ch.start, ch.start + n)
                row_of[cursor:cursor + n] = s
                temp[s] = req.temperature
                top_p[s] = req.top_p
                bt[s, :len(req.blocks)] = req.blocks
                cursor += n
            n_prefill = cursor - len(running)
            # pages the attention kernel has to read: its live visits
            live_pages = int(np.sum(
                -(-cl[ql > 0] // cfg.block_size))) if on else 0
            # rows that ask for a draw: with none, the step's sampler
            # skips its lane (idle slots stay at temperature 0)
            sampled_rows = int(np.count_nonzero(temp > 0)) if on else 0
            step_attrs = self._model_step_attrs(qs, ql, cl, cursor) if on \
                else None
            self._key, sub = jax.random.split(self._key)
        # outside the retried body: the arrays are immutable, so a
        # retry of the dispatch re-uses them
        with span("serving.transfer"):
            toks, pos, row_of, qs, ql, cl, bt, temp, top_p, from_prev = (
                jnp.asarray(a) for a in
                (toks, pos, row_of, qs, ql, cl, bt, temp, top_p, from_prev))
        prev = self._no_tokens if older is None else older.nxt
        compiles, t0 = self.ragged_compiles, time.perf_counter()
        with span("serving.ragged_step",
                  args={"rows": len(running) + len(chunks),
                        "tokens": cursor, "impl": self.attention_impl,
                        "kv_write": self.kv_write_impl,
                        "live_pages": live_pages,
                        "sampled_rows": sampled_rows,
                        "passes": self._ad.passes,
                        "cache_layers": self._ad.cache_layers,
                        "weight_bytes": self._pass_weight_bytes,
                        "in_flight": int(older is not None),
                        **step_attrs}
                  if on else None):
            nxt, self._kp, self._vp = self._dispatch(
                lambda: self._ragged_fn(
                    self._w, toks, pos, row_of, qs, ql, cl, self._kp,
                    self._vp, bt, temp, top_p, sub, prev, from_prev))
        for req in running:
            req.in_flight += 1
        for ch in chunks:
            req = ch.req
            req.prefilled = ch.start + len(ch.tokens)
            if ch.last:
                req.in_flight += 1
                if not req.handoff:      # a hand-off parks on this token
                    req.state = RUNNING
        if on:
            if self.ragged_compiles > compiles:
                # trace and compile ran inside that dispatch; whether
                # the pools were donated says whether KV is written in
                # place
                _compile_ledger.note_compile(
                    "serving.ragged_step",
                    duration_s=time.perf_counter() - t0,
                    donated_args=self._donated_args)
            _obs.registry.counter("serving.ragged_steps").inc()
            if older is not None:
                _obs.registry.counter("serving.lookahead_steps").inc()
            if sampled_rows:
                _obs.registry.counter("serving.sampled_steps").inc()
            if running:
                _obs.registry.counter("serving.decode_tokens").inc(
                    len(running))
            if n_prefill:
                _obs.registry.counter("serving.prefill_tokens").inc(
                    n_prefill)
            if step_attrs.get("state_resets"):
                _obs.registry.counter("serving.ssm_state_resets").inc(
                    step_attrs["state_resets"])
        return _Flight(nxt, running, chunks, cursor), cursor

    def _collect(self, flight: _Flight) -> None:  # ptlint: holds=_lock
        """The round's second half: wait for a launched step's tokens
        and emit them. A row whose request ended after the launch
        (``eos``, ``cancel()``, a deadline: ends the host could not
        foresee) is dropped; its KV write went to a page the request
        still owned at launch, which no later step reads before writing.

        Spans, in order: ``serving.device_wait`` (the ``np.asarray`` of
        the step's tokens; a model with expert layers, read with the
        tokens: ``moe_pairs_held``, the step's pairs dispatched to experts
        held here, beside ``moe_pairs_routed``, its tokens x k x expert
        layers, and ``moe_blocks_live``, the row blocks its grouped
        matmuls computed, beside the static ``moe_blocks``),
        ``serving.emit``."""
        on = _obs.enabled()
        with span("serving.device_wait") as sp:
            out = np.asarray(flight.nxt)
            if on and self._moe_layers:
                held, live = (int(n) for n in out[-2:])
                blocks = self._step_attrs["moe_blocks"]
                sp.set_arg("moe_pairs_held", held)
                sp.set_arg("moe_pairs_routed",
                           self._pairs_routed(flight.tokens))
                sp.set_arg("moe_blocks_live", live)
                sp.set_arg("moe_blocks", blocks)
                _obs.registry.counter("serving.moe_pairs_held").inc(held)
                _obs.registry.counter("serving.moe_blocks_skipped").inc(
                    blocks - live)
        with span("serving.emit") as sp:
            emitted = overrun = 0
            for req in flight.running:
                if req.state == RUNNING:     # did not end mid-flight
                    self._emit(req, int(out[req.slot]))
                    emitted += 1
                else:
                    overrun += 1
            for ch in flight.chunks:
                req = ch.req
                if req.state not in (PREFILL, RUNNING):
                    overrun += 1             # cancelled mid-flight
                    continue
                if not ch.last:
                    continue
                # the first token is emitted where the step of the final
                # chunk is collected; TTFT is observed once per request
                # (a preempted request re-prefills but already streamed
                # its first token)
                if req.first_token_at is None:
                    req.first_token_at = time.monotonic()
                    if on:
                        _obs.registry.histogram("serving.ttft").observe(
                            req.first_token_at - req.arrival)
                if req.timeline is not None:
                    req.timeline.mark_running()
                if req.handoff:
                    # disaggregated prefill: park for take_handoff(); the
                    # pages stay resident until the payload is exported
                    req.state = HANDOFF
                    req.in_flight -= 1
                    req.handoff_token = int(out[req.slot])
                    self._handoff_ready.append(req)
                else:
                    self._emit(req, int(out[req.slot]))
                    emitted += 1
            if on:
                sp.set_arg("tokens", emitted)
                if overrun:
                    _obs.registry.counter("serving.overrun_rows").inc(
                        overrun)

    def _drain(self, reason: str) -> None:  # ptlint: holds=_lock
        """Collect the step in flight, if there is one, before something
        that needs every launched token on the host or the pools at
        rest: a preempting round, a hand-off, a prefix export or import,
        the engine's end."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        if _obs.enabled():
            _obs.registry.counter("serving.drained_rounds",
                                  tags={"reason": reason}).inc()
        self._collect(flight)

    def _settle(self) -> None:  # ptlint: holds=_lock
        """:meth:`_drain` for the engine's end: a step that failed on
        the device must not keep the streams from being ended."""
        try:
            self._drain("shutdown")
        except Exception:
            traceback.print_exc()

    def _emit(self, req: Request, tok: int) -> None:  # ptlint: holds=_lock
        req.generated.append(tok)
        req.remaining -= 1
        req.in_flight -= 1
        if req.timeline is not None:
            req.timeline.mark_emit()
        q = self._streams.get(req.rid)
        if q is not None:
            q.put(("tok", tok))
        if req.eos_id is not None and tok == req.eos_id:
            self.scheduler.finish(req, "eos")
            self._end_stream(req, "eos")
        elif req.remaining <= 0:
            self.scheduler.finish(req, "length")
            self._end_stream(req, "length")

    def _end_stream(self, req: Request, reason: str) -> None:  # ptlint: holds=_lock
        q = self._streams.get(req.rid)
        if q is not None:
            q.put(("end", reason))
        if req.timeline is not None:
            req.timeline.close(reason)
        if _obs.enabled():
            _obs.registry.counter("serving.requests",
                                  tags={"outcome": reason}).inc()

    def _expire_deadlines(self) -> None:  # ptlint: holds=_lock
        now = time.monotonic()
        for req in list(self._requests.values()):
            if req.deadline is not None and now > req.deadline and \
                    req.state not in ("finished", "cancelled"):
                self.scheduler.cancel(req, "deadline")
                self._end_stream(req, "deadline")
                if _obs.enabled():
                    _obs.registry.counter(
                        "serving.deadline_cancels").inc()

    # -------------------------------------------------- lifecycle/thread
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            try:
                while not self._stop.is_set():
                    if not self.step():
                        self._wakeup.wait(timeout=0.01)
                        self._wakeup.clear()
            except Exception as e:
                # the loop thread is the only consumer of the scheduler:
                # if a step fails past its retries (a compile error, a
                # device fault) every open stream would otherwise block
                # in q.get() for ever. Mark the engine dead and end them
                # all with the error, so stream() raises RequestError.
                traceback.print_exc()
                self.fail_all("engine_error: %s: %s"
                              % (type(e).__name__, e))

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serving-engine")
        self._thread.start()

    def shutdown(self, check_leaks: bool = True) -> None:
        """Stop the loop, cancel outstanding requests, and verify the
        block pool drained (every page free or prefix-cached)."""
        self._stop.set()
        self._wakeup.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        with self._lock:
            self._settle()
            for req in list(self._requests.values()):
                if req.state not in ("finished", "cancelled"):
                    self.scheduler.cancel(req, "shutdown")
                    self._end_stream(req, "shutdown")
            if check_leaks:
                self.manager.assert_all_free()
