"""Request-centric continuous-batching engine over the hybrid KV pool.

The engine is the "operating system" of the serving stack (paper §5.6),
fronted by a request-centric API:

* ``EngineConfig`` — immutable construction options (replaces the old
  12-kwarg constructor pile; the legacy kwargs still work through a
  deprecation shim that warns once);
* ``Request`` — an immutable submission (prompt, ``SamplingParams``,
  priority, eos/max_tokens).  All mutable per-request runtime state
  (generated tokens, done flag, per-request translation telemetry)
  lives in an engine-internal ``RequestState`` and is surfaced through
  ``RequestOutput`` snapshots from ``Engine.poll()`` / ``stream()``;
* admission — a pluggable :class:`~repro.serve.scheduler.Scheduler`
  (FIFO / shortest-prompt-first / priority-with-aging) orders waiting
  requests; the engine itself owns budgets, chunking, slot registration
  and prefix sharing.  Every ``step()`` admits up to the per-step
  prefill token budget, bucketing variable-length prompts into padded
  power-of-two length buckets (bounded compile shapes) and installing
  ALL admitted sequences' KV blocks with one batched prefill dispatch
  per bucket.  Prompts longer than the budget are *chunked* so a long
  prompt interleaves with decode instead of stalling it; chunks k > 0
  run the PREFIX-KV step (serve/prefill.py) — only the chunk's own
  tokens are forwarded, attention reads the prefix from the installed
  pool blocks and recurrent layers continue saved state, so chunk cost
  is linear in chunk length (``prefill_mode="recompute"`` keeps the
  full-re-forward path as the correctness oracle);
* sampling — per-request temperature / top-k / top-p with per-slot PRNG
  keys runs IN-GRAPH (serve/sampling.py): the engine scatters a
  request's SamplingParams into per-slot device arrays at admission and
  both jitted steps emit token ids, so the per-step fetch stays O(B)
  token ids.  Greedy (temperature 0) is the fast path, bit-identical to
  the pre-sampling engine;
* steady state: every decode step (i) allocates the current block when a
  sequence crosses a block boundary, (ii) scatters the *dirty deltas* of
  TAR/SF/flex to the device, (iii) runs the jitted serve_step — which
  translates once and returns translation telemetry as an auxiliary
  output, (iv) feeds that telemetry back to the manager globally AND
  attributed per request (``stats()["per_request"]``), (v) applies
  pending slot migrations as ONE batched gather/scatter (Fig. 16);
* speculative decoding (``spec_decode="ngram"``, serve/spec_decode.py):
  every decode dispatch verifies K self-drafted tokens and commits all
  leading matches plus one bonus token — variable-length advance,
  rejected-tail block dealloc and eos/max-token truncation rewinds are
  the engine's commit job; LOSSLESS (greedy and seeded-sampled streams
  are token-identical to spec-off) and the fetch below stays single;
* termination: ``max_new_tokens`` ("length") or ``eos_token`` ("stop");
  with ``auto_release=True`` the slot and KV blocks free immediately and
  recycle under sustained load;
* prefix sharing: an AUTOMATIC content-addressed prefix cache
  (core/prefix_cache.py, ``EngineConfig.prefix_cache``, on by default)
  hash-chains every installed prompt block into a set-associative
  directory — the paper's restrictive mapping reused as a
  content->physical map — so any later request sharing a prompt prefix
  attaches the same physical blocks read-only (FlexSeg refcounts — the
  paper's inter-process page sharing) and prefills only its tail;
  unreferenced cache entries are the cheapest reclaim rung under
  capacity pressure, and streams stay bit-identical to cache-off;
* eviction/swap: pool exhaustion surfaces as swap events exactly as in
  the restrictive-only experiment (Fig. 9);
* overload (ISSUE 6, DESIGN.md §tiered-KV-and-overload): when a KV
  block cannot be allocated the engine walks the degradation ladder —
  admit less, chunk, PREEMPT a victim sequence to the host KV tier
  (``preempt_request``: one batched gather of its blocks + recurrent /
  cross / history rows), and only rejects requests that can never run.
  Preempted requests re-enter the scheduler queue with their original
  arrival and resume bit-identically (KV restored bitwise, sampling
  keys re-derived from (seed, seq_id) and folded with absolute
  position); a ``runtime.fault.ServeFaultInjector`` can force
  allocation failures and preemptions at the step's safe points for
  chaos testing.

Both steady-state contracts survive preemption: translation still
happens once inside the dispatch, and a steady step still performs ONE
``device_get`` — ``preempt_request`` adds its own batched gather only
when a victim is actually swapped, never on the untriggered path.

Hot-path contract (DESIGN.md §translate-once): the steady-state
``step()`` performs a BOUNDED number of host<->device transfers — at
most three dirty-delta scatters, two pool copy dispatches, the step
dispatch itself, and ONE device_get — independent of batch size,
sequence count, or pending-copy count.  Admission steps add one prefill
dispatch per length bucket plus the sampling-state scatters, but the
fetch stays single: prefill first-tokens ride in the same ``device_get``
as the decode telemetry.

Single-host configuration (G = 1 data group); the SPMD prefill/decode
steps in serve/prefill.py and serve/decode.py are the same code the
launcher shards across a pod.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import pickle
import time
import warnings
from collections import defaultdict
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import (HybridConfig, HybridKVManager, Partition,
                        PoolExhausted, PrefixCache, SWAP, CHAIN_SEED,
                        block_hash_chain)
from repro.dist.sharding import kv_state_specs
from repro.models import FwdOptions, model_dims
from .decode import DecodeSpec, make_serve_step, init_decode_state
from .prefill import make_prefill_step, make_prefix_prefill_step
from .sampling import GREEDY, SamplingParams, prng_key_data
from .scheduler import Scheduler, make_scheduler
from .spec_decode import make_spec_decode_step

# Host spans of the engine's phases in the profiler's own trace (the
# benchmark attributes device-idle time to them).  With the profiler off
# a span costs its enter and exit; names are constants, so no string or
# kwargs is built per step.
_span = jax.profiler.TraceAnnotation

# MoE executions kept in ``stats()["moe"]["steps"]``: minutes of serving
MOE_LOG_STEPS = 16384


def _pad_pow2(idx: np.ndarray, fill) -> np.ndarray:
    """Pad an index vector to the next power of two (bounded set of XLA
    scatter shapes: without this every distinct dirty/copy count compiles
    a fresh executable, which dwarfs the dispatch it feeds)."""
    n = 1 << max(0, int(idx.size - 1).bit_length())
    if n == idx.size:
        return idx
    return np.concatenate([idx, np.full(n - idx.size, fill, idx.dtype)])


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


@jax.jit
def _scatter_delta(tar, sf, flex, sets_idx, tar_rows, sf_rows, flex_idx,
                   flex_vals):
    """Apply one dirty-delta sync as a SINGLE jitted dispatch.

    The pre-fix path issued up to three eager ``.at[].set`` calls per
    sync; under speculative decoding — where block dealloc/realloc
    dirties the tables almost every step — the per-op python dispatch
    overhead of those eager scatters dominated the verify dispatch
    itself (~5 ms/step measured on CPU).  Indices are pow2-padded by the
    caller (bounded executable set, keyed by the two pad lengths);
    out-of-bounds sentinel indices drop, so an empty side of the delta
    costs one dropped row.
    """
    tar = tar.at[0, sets_idx].set(tar_rows, mode="drop")
    sf = sf.at[0, sets_idx].set(sf_rows, mode="drop")
    flex = flex.at[0, flex_idx].set(flex_vals, mode="drop")
    return tar, sf, flex


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_pool_slots(k_pool, v_pool, dst, src=None, kv=None):
    """Write slots ``dst`` of both KV pools in place, as ONE dispatch that
    donates the pools: from slots ``src`` of the same pools (slot
    migrations; the gather reads the pre-write contents) or from ``kv``,
    the stacked ``(2, L, n, ...)`` blocks of a resumed sequence.  An
    eager ``pool.at[].set`` would write a whole new pool per call.
    Indices are pow2-padded by the caller (bounded executable set)."""
    if src is not None:
        k_rows, v_rows = k_pool[:, src], v_pool[:, src]
    else:
        k_rows, v_rows = kv[0], kv[1]
    return k_pool.at[:, dst].set(k_rows), v_pool.at[:, dst].set(v_rows)


# ------------------------------------------------------------- request API

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine construction options.

    ``scheduler`` is a policy name (``"fifo"`` / ``"spf"`` /
    ``"priority"``), a ready Scheduler instance, or a zero-arg factory.
    ``prefill_budget`` is NEW prompt tokens admitted per step (None =
    ``4 * block_size * max_batch``).
    """
    max_batch: int = 4
    max_seq_len: int = 256
    pool_headroom: float = 1.25
    mode: str = "hybrid"
    attn_impl: str = "dense"
    dtype: Any = jnp.float32
    restseg_fraction: float = 0.75
    track_stats: bool = True
    prefill_budget: Optional[int] = None
    auto_release: bool = False
    scheduler: Any = "fifo"
    # how chunks k > 0 of a budget-split prompt are prefilled:
    # "prefix_kv" forwards ONLY the chunk's tokens, attending over the
    # prefix's installed pool blocks (linear chunk cost); "recompute" is
    # the PR-2 full-prefix re-forward — the correctness oracle the
    # differential suite pins prefix_kv against
    prefill_mode: str = "prefix_kv"
    # prefix-KV pool read: "exact" (bit-identical dense gather) or
    # "paged" (Q>1 paged-attention read + online-softmax merge)
    prefix_gather: str = "exact"
    # speculative decoding (serve/spec_decode.py): None/False = off (the
    # default — spec-off is bit-identical to the pre-spec engine);
    # "ngram" (or True) = self-drafted n-gram / prompt-lookup drafter,
    # ``num_draft_tokens`` drafts verified per decode dispatch.  Greedy
    # AND seeded-sampled streams stay token-identical to spec-off
    # (lossless verification); recurrent (ssm/hybrid) families fall back
    # to non-speculative decode with a warn-once.
    spec_decode: Any = None
    num_draft_tokens: int = 4
    spec_ngram: int = 2
    # overload behaviour when a KV block cannot be allocated (ISSUE 6):
    # "preempt" (default) swaps a victim sequence out to the host tier
    # and re-admits it through the scheduler queue — poll()/stream()
    # make progress instead of raising; "fail" is the fail-fast
    # baseline: admission defers until the request's full footprint
    # fits and a decode-time miss raises PoolExhausted (it also fixes
    # the pre-overload silent corruption where a SWAP'd current block
    # dropped its KV write behind a masked w_valid)
    overload_policy: str = "preempt"
    # a runtime.fault.ServeFaultInjector (or None): forced allocation
    # failures and preemptions for the chaos suite
    fault_injector: Any = None
    # SPMD serving (DESIGN.md §sharded-serving): ``(data, model)`` builds
    # a local mesh; the KV pool and TAR/SF/flex tables shard over the
    # model axis (set-index / block-range partitioning), every step runs
    # once per shard under one shard_map, and token streams stay bitwise
    # identical to ``mesh_shape=None``.  The data axis replicates the
    # engine state (it scales compute only, so data > 1 requires no
    # state changes).  None = the single-device engine, trace-identical
    # to every pre-SPMD release.
    mesh_shape: Optional[Tuple[int, int]] = None
    # Utopia-native global prefix cache (core/prefix_cache.py): "auto"
    # (default) builds a PrefixCache whenever the configuration supports
    # it (attention KV blocks + a flexible segment; silently off
    # otherwise), True demands it (raises where unsupported), None/False
    # disables it, and a ready PrefixCache instance is used as-is.
    # Enabled, every submitted prompt automatically attaches its longest
    # cached prefix read-only and only the tail runs prefill; token
    # streams stay bit-identical to a cache-off run.
    prefix_cache: Any = "auto"
    # a serve.metrics.MetricsLogger (or None): the engine feeds it one
    # host-side event per step (counter deltas + occupancy gauges) and
    # request submit/finish lifecycle events.  Purely observational —
    # no device operation, token streams bit-identical logger-on vs
    # logger-off (pinned in tests/test_metrics.py).
    metrics: Any = None


class ChunkRecord(NamedTuple):
    """One admitted prompt chunk in ``Engine.admission_log``.

    ``fwd_tokens`` is the number of tokens actually fed through the chunk
    forward: ``end - start`` on the prefix-KV path (constant in chunk
    index — the linearity contract), ``frontend + end`` on the recompute
    path (grows with every chunk).
    """
    seq_id: int
    start: int
    end: int
    path: str          # "prefix_kv" | "recompute"
    fwd_tokens: int


@dataclasses.dataclass(frozen=True, eq=False)
class Request:
    """Immutable request submission.

    The prompt (and frontend) arrays are defensively copied and marked
    read-only at construction.  Runtime state — generated tokens, the
    done flag, finish reason, per-request telemetry — lives in the
    engine's internal ``RequestState``; consume it via the
    ``RequestOutput`` snapshots that ``Engine.poll()`` returns.  The
    ``generated`` / ``done`` properties remain readable for pre-redesign
    call sites: after submission they proxy the engine-held state.
    """
    seq_id: int
    prompt: np.ndarray
    frontend: Optional[np.ndarray] = None
    max_new_tokens: int = 16
    eos_token: Optional[int] = None
    sampling: SamplingParams = GREEDY
    priority: int = 0
    # wall-clock budget from submission, in milliseconds (None = no
    # deadline).  ``poll()`` cancels the request — wherever it is:
    # queued, mid-chunk prefill, decoding, or parked on the host tier —
    # once the budget elapses, releasing its slot, cache pins and
    # ledger claims; the stream finishes with finish_reason="deadline".
    # The clock is the monotonic ``time.perf_counter`` (NTP-immune);
    # across a snapshot/restore the REMAINING budget carries over.
    deadline_ms: Optional[float] = None

    def __post_init__(self):
        p = np.array(self.prompt, copy=True)
        p.setflags(write=False)
        object.__setattr__(self, "prompt", p)
        if self.frontend is not None:
            f = np.array(self.frontend, copy=True)
            f.setflags(write=False)
            object.__setattr__(self, "frontend", f)

    # -- compatibility views over the engine-held state ------------------
    @property
    def generated(self) -> List[int]:
        st = getattr(self, "_engine_state", None)
        return st.generated if st is not None else []

    @property
    def done(self) -> bool:
        st = getattr(self, "_engine_state", None)
        return st.done if st is not None else False


@dataclasses.dataclass
class RequestState:
    """Engine-internal mutable per-request state."""
    request: Request
    arrival: int                     # engine step at submission (aging)
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # "stop" | "length" | "cancelled" | "deadline"
    finish_reason: Optional[str] = None
    # absolute monotonic deadline (perf_counter seconds), set at submit
    # from Request.deadline_ms; snapshot/restore rebases it so only the
    # REMAINING budget survives a crash
    deadline_at: Optional[float] = None
    new_tokens: List[int] = dataclasses.field(default_factory=list)
    finish_reported: bool = False
    # per-request translation telemetry (stats()["per_request"])
    rsw_hits: int = 0
    flex_walks: int = 0
    swap_faults: int = 0
    # speculative-decode telemetry: drafts proposed for / accepted into
    # this request's stream (rows sum exactly to the engine's global
    # spec_drafted / spec_accepted counters)
    drafted: int = 0
    accepted: int = 0
    # prefix-cache hits: blocks attached from the cache at admission
    # (rows sum exactly to the global dedup_blocks counter)
    cached_blocks: int = 0
    # overload bookkeeping: step of the latest commit (the LRU key for
    # victim selection) and how often this request was preempted /
    # resumed.  The aggregates in stats()["overload"] are SEPARATE
    # monotone engine counters, not sums over these rows: a finished
    # request's row is dropped on seq_id reuse, and a global that
    # summed rows would silently shrink (rows + dropped == global is
    # the pinned invariant).
    last_step: int = 0
    preempts: int = 0
    resumes: int = 0
    # queue wait on ``time.perf_counter`` (the clock of ``deadline_at``):
    # submit -> first prompt chunk admitted.  A resume after preemption
    # re-enters the queue but leaves both fields as they were.
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None


@dataclasses.dataclass
class _HostTierSeq:
    """One preempted sequence parked in host memory (the KV tier).

    Everything the sequence needs to continue bit-identically: the pool
    blocks it had mapped (``kv`` stacked as (2=k/v, L_attn, n_blocks,
    block, KV, hd) in pool dtype — a bitwise round-trip), its per-slot
    recurrent/cross-attention rows, the spec-decode history row, the
    committed context length and — for a mid-prefill victim — how many
    prompt tokens were installed.  Sampling state needs no save: per-slot
    PRNG keys derive from (seed, seq_id) and fold the absolute position,
    so they are re-scattered on resume (PR-3 invariant)."""
    seq_id: int
    ctx: int
    prefill_progress: Optional[int]     # tokens installed, None = done
    blocks: List[Tuple[int, bool]]      # (block_idx, writable) at preempt
    kv: Optional[np.ndarray]
    rows: Dict[str, np.ndarray]         # ssm/conv/cross_k/cross_v/hist
    nbytes: int


@dataclasses.dataclass(frozen=True)
class RequestOutput:
    """Streaming snapshot for one request, drained by ``Engine.poll()``.

    ``new_token_ids`` — tokens produced since the previous poll;
    ``token_ids`` — all tokens generated so far; ``finish_reason`` —
    ``"stop"`` (eos), ``"length"`` (max_new_tokens), ``"cancelled"``
    (``Engine.cancel``) or ``"deadline"`` (``Request.deadline_ms``
    elapsed) once finished.
    """
    seq_id: int
    new_token_ids: Tuple[int, ...]
    token_ids: Tuple[int, ...]
    finished: bool
    finish_reason: Optional[str]


# ------------------------------------------------------- crash-safe snapshot

SNAPSHOT_VERSION = 1


@dataclasses.dataclass(frozen=True)
class EngineSnapshot:
    """Complete serving state at a step boundary (DESIGN.md
    §crash-recovery).

    ``dstate`` holds HOST (numpy) copies of every decode-state device
    array EXCEPT the TAR/SF/flex translation mirrors — the host tables
    inside ``host_blob`` are authoritative for those, and
    ``Engine.restore`` rebuilds the device mirrors through the existing
    full-sync path.  ``host_blob`` pickles the whole host side in one
    dump (manager + prefix cache + scheduler + request states + the
    host KV tier + monotone counters), so shared references — the
    cache's manager pointer, a ``Request`` reachable from both the
    scheduler queue and ``_states`` — survive as the SAME object on
    restore.

    ``to_arrays``/``from_arrays`` flatten to a ``{name: ndarray}`` dict
    for ``ckpt.CheckpointManager.save_named`` (the host blob's length
    varies per snapshot, which the positional checkpoint API's shape
    check forbids).
    """
    version: int
    step: int
    dstate: Dict[str, np.ndarray]
    host_blob: bytes

    def to_arrays(self) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {
            "meta": np.asarray([self.version, self.step], np.int64),
            "host": np.frombuffer(self.host_blob, np.uint8),
        }
        for k, v in self.dstate.items():
            out[f"d.{k}"] = np.asarray(v)
        return out

    @classmethod
    def from_arrays(cls, arrays: Dict[str, np.ndarray]
                    ) -> "EngineSnapshot":
        meta = np.asarray(arrays["meta"])
        return cls(
            version=int(meta[0]), step=int(meta[1]),
            dstate={k[2:]: np.asarray(v) for k, v in arrays.items()
                    if k.startswith("d.")},
            host_blob=np.asarray(arrays["host"]).tobytes())


_LEGACY_KWARGS_WARNED = False
_SPEC_FALLBACK_WARNED = False
_SHARE_KWARG_WARNED = False


def _warn_share_kwarg() -> None:
    global _SHARE_KWARG_WARNED
    if _SHARE_KWARG_WARNED:
        return
    _SHARE_KWARG_WARNED = True
    warnings.warn(
        "submit(share_prefix_from=..., shared_blocks=...) is deprecated: "
        "the engine's content-addressed prefix cache "
        "(EngineConfig.prefix_cache, on by default) dedupes shared "
        "prompt prefixes automatically — the kwargs are accepted but "
        "the cache decides what is shared; with the cache disabled the "
        "prompt is simply recomputed (sharing was always best-effort)",
        DeprecationWarning, stacklevel=3)


def _warn_spec_fallback(family: str) -> None:
    global _SPEC_FALLBACK_WARNED
    if _SPEC_FALLBACK_WARNED:
        return
    _SPEC_FALLBACK_WARNED = True
    warnings.warn(
        f"speculative decoding is not supported for recurrent family "
        f"{family!r} (ssm/conv state rollback for rejected drafts is "
        "not cheap — ROADMAP item); falling back to non-speculative "
        "decode", stacklevel=3)


def _warn_legacy_kwargs(kwargs) -> None:
    global _LEGACY_KWARGS_WARNED
    if _LEGACY_KWARGS_WARNED:
        return
    _LEGACY_KWARGS_WARNED = True
    warnings.warn(
        f"Engine(cfg, params, {', '.join(sorted(kwargs))}=...) kwargs are "
        "deprecated; pass Engine(cfg, params, EngineConfig(...)) instead",
        DeprecationWarning, stacklevel=3)


# ------------------------------------------------------------------ engine

class Engine:
    def __init__(self, cfg: ArchConfig, params,
                 config: Optional[EngineConfig] = None, **legacy):
        if legacy:
            if config is not None:
                raise TypeError("pass an EngineConfig OR legacy kwargs, "
                                "not both")
            known = {f.name for f in dataclasses.fields(EngineConfig)}
            unknown = set(legacy) - known
            if unknown:
                raise TypeError(f"unknown Engine kwargs {sorted(unknown)}")
            _warn_legacy_kwargs(legacy)
            config = EngineConfig(**legacy)
        elif config is None:
            config = EngineConfig()
        self.config = config
        self.cfg = cfg
        self.dims = model_dims(cfg, tp=1)
        self.params = params
        bs = cfg.kv_block_size
        max_batch, max_seq_len = config.max_batch, config.max_seq_len
        max_blocks = max_seq_len // bs
        self.hybrid_cfg = HybridConfig(
            block_size=bs,
            total_slots=max(16, int(max_batch * max_blocks
                                    * config.pool_headroom) // 8 * 8),
            restseg_fraction=config.restseg_fraction, assoc=8,
            max_seqs=max_batch, max_blocks_per_seq=max_blocks,
            mode=config.mode)
        self.track_stats = config.track_stats
        self.manager = HybridKVManager(self.hybrid_cfg)
        if config.prefill_mode not in ("prefix_kv", "recompute"):
            raise ValueError(f"unknown prefill_mode {config.prefill_mode!r}"
                             " (expected 'prefix_kv' or 'recompute')")
        self.prefill_mode = config.prefill_mode
        if self.prefill_mode == "prefix_kv" and config.attn_impl != "dense":
            # the prefix chunk forward implements the dense softmax; mixing
            # it with a flash/pallas chunk-0 forward would let chunk k>0
            # drift from the recompute oracle in float summation order
            warnings.warn(
                f"prefix-KV chunked prefill is defined against the dense "
                f"attention forward; falling back to "
                f"prefill_mode='recompute' for attn_impl="
                f"{config.attn_impl!r}", stacklevel=2)
            self.prefill_mode = "recompute"
        # ---- SPMD mesh / partition (DESIGN.md §sharded-serving) ---------
        self.mesh = None
        self.partition: Optional[Partition] = None
        kv_shards = 0
        if config.mesh_shape is not None:
            from repro.launch.mesh import make_local_mesh
            data, model = config.mesh_shape
            self.mesh = make_local_mesh(data=data, model=model)
            # kv_shards >= 1 selects the SPMD layout even at model == 1
            # (same code path regardless of shard count); the data axis
            # replicates state, so the partition covers the model axis
            kv_shards = int(model)
            self.partition = Partition.for_hybrid(self.hybrid_cfg, model)
            self.manager.set_partition(self.partition)
        self.spec = DecodeSpec(
            block_size=bs, max_blocks_per_seq=max_blocks,
            slots_per_group=self.hybrid_cfg.total_slots,
            n_sets=self.hybrid_cfg.num_sets, assoc=self.hybrid_cfg.assoc,
            mode="batch", hash_name=self.hybrid_cfg.hash_name,
            prefix_gather=config.prefix_gather, kv_shards=kv_shards)
        dtype = config.dtype
        self.dstate = init_decode_state(cfg, self.dims, self.spec,
                                        max_batch, 1, dtype=dtype,
                                        part=self.partition)
        self.max_batch = max_batch
        # tokens of NEW prompt admitted per step; chunk granularity is the
        # KV block, so the effective budget is floor(budget / bs) blocks
        self.prefill_budget = (config.prefill_budget
                               if config.prefill_budget is not None
                               else 4 * bs * max_batch)
        if self.prefill_budget < bs:
            raise ValueError(
                f"prefill_budget {self.prefill_budget} is smaller than "
                f"the KV block size {bs}: no prompt chunk could ever be "
                "admitted")
        self.auto_release = config.auto_release
        if config.overload_policy not in ("preempt", "fail"):
            raise ValueError(
                f"unknown overload_policy {config.overload_policy!r} "
                "(expected 'preempt' or 'fail')")
        self.overload_policy = config.overload_policy
        self._injector = config.fault_injector
        # host KV tier: preempted sequences parked off-device (ISSUE 6)
        self._preempted: Dict[int, _HostTierSeq] = {}
        self._swap_bytes_out = 0
        self._swap_bytes_in = 0
        # per-shard swap traffic (mesh only): KV bytes attributed to the
        # shard owning each swapped block, non-pool rows to shard 0 —
        # the shard rows sum EXACTLY to the global counters
        n_sh = self.partition.n_shards if self.partition else 1
        self._shard_swap_out = np.zeros(n_sh, np.int64)
        self._shard_swap_in = np.zeros(n_sh, np.int64)
        # monotone count of preempt/resume events: poll()'s no-progress
        # detector treats any of them as progress (a step that only
        # rearranges residency is not a stuck step)
        self._progress_events = 0
        # monotone engine-level preempt/resume counters.  These are NOT
        # derived from the per-request rows: a finished request's state
        # is dropped when its seq_id is reused, so a sum over
        # ``self._states`` silently loses counts.  The dropped share is
        # tracked too — sum(rows) + dropped == global is an invariant
        # ``check_invariants`` asserts.
        self._request_preempts = 0
        self._request_resumes = 0
        self._dropped_preempts = 0
        self._dropped_resumes = 0
        # monotone count of tokens committed to any stream (decode,
        # spec commit, prefill first-tokens): the metrics logger's
        # per-step tokens delta and the dashboard tokens/s numerator
        self._tokens_emitted = 0
        # MoE telemetry, fetched with each step's one device_get: per
        # program kind ("decode" / "prefill") executions, routed (token,
        # expert) pairs and distinct experts hit, summed over MoE layers;
        # ``_moe_log`` keeps the last steps' readings with their fetch
        # times for window-bounded readers
        self._moe_totals = {k: {"executions": 0, "routed_pairs": 0,
                                "experts_hit": 0}
                            for k in ("decode", "prefill")}
        self._moe_pending: List[Any] = []
        self._moe_log: Deque[Tuple[float, str, int, int]] = \
            collections.deque(maxlen=MOE_LOG_STEPS)
        # request-lifecycle monotone counters (ISSUE 10): explicit
        # cancellations and wall-clock deadline expiries
        self._cancelled = 0
        self._deadline_expired = 0
        # live metrics stream (serve/metrics.py): fed one host-side
        # event per step; None = zero overhead on the hot path
        self.metrics = config.metrics
        self.scheduler: Scheduler = make_scheduler(config.scheduler)
        # a scheduler instance is MUTABLE state: sharing one between two
        # engines (e.g. via a reused EngineConfig holding an instance)
        # would let engine B admit — and decode with B's params — a
        # request submitted to engine A
        if getattr(self.scheduler, "_bound_engine", None) is not None:
            raise ValueError(
                "scheduler instance is already bound to another Engine; "
                "pass a policy name or factory in EngineConfig instead")
        try:
            self.scheduler._bound_engine = self
        except AttributeError:
            pass                       # slotted/frozen scheduler: skip
        self.fwd = FwdOptions(attn_impl=config.attn_impl, dtype=dtype,
                              collect_cache=True)
        # Every step program takes the decode state (argument 1) as a
        # donated argument: its outputs alias the input buffers, so a step
        # updates the KV pools in place instead of first copying both.
        # The engine is the state's only holder and rebinds ``dstate`` to
        # each step's output; the pre-step arrays are deleted.
        # ``sample`` is static: at most two cached executables (all-greedy
        # / any-sampled); the all-greedy one is the pre-sampling argmax
        # hot path, with no sort/softmax/gumbel in the trace
        self._serve_step = jax.jit(make_serve_step(
            cfg, self.dims, self.spec, mesh=self.mesh, dtype=dtype,
            part=self.partition),
            static_argnames=("sample",), donate_argnums=(1,))
        # one jitted callable; XLA re-specializes per (bucket_B, bucket_S)
        # — both power-of-two padded, so the executable set is bounded
        self._prefill_step = jax.jit(make_prefill_step(
            cfg, self.dims, self.spec, mesh=self.mesh, fwd=self.fwd,
            part=self.partition),
            static_argnames=("sample",), donate_argnums=(1,))
        # prefix-KV chunk step: chunks k > 0 forward only their own tokens
        # and read the prefix from the pool (shapes keyed additionally by
        # the pow2 prefix-buffer width — still a bounded set)
        self._prefix_step = jax.jit(make_prefix_prefill_step(
            cfg, self.dims, self.spec, mesh=self.mesh, fwd=self.fwd,
            part=self.partition),
            static_argnames=("sample",), donate_argnums=(1,))
        # pool writes outside the step programs (``_write_pool``):
        # dispatches of ``_write_pool_slots`` and the slots they wrote
        self._pool_writes = {"dispatches": 0, "slots": 0}
        # ---- speculative decoding (serve/spec_decode.py) ----------------
        sd = config.spec_decode
        if sd is True:
            sd = "ngram"
        if sd not in (None, False, "ngram"):
            raise ValueError(f"unknown spec_decode drafter {sd!r} "
                             "(expected None/False or 'ngram')")
        self.spec_K = 0
        if sd:
            if cfg.family in ("ssm", "hybrid"):
                # state rollback for rejected drafts is not cheap:
                # warn once and keep the non-speculative step
                _warn_spec_fallback(cfg.family)
            else:
                if config.num_draft_tokens < 1:
                    raise ValueError("num_draft_tokens must be >= 1, got "
                                     f"{config.num_draft_tokens}")
                if config.spec_ngram < 1:
                    # a non-positive n-gram would silently degrade the
                    # drafter to repeat-current-token (all-rejected
                    # worst case) — loud error, like num_draft_tokens
                    raise ValueError("spec_ngram must be >= 1, got "
                                     f"{config.spec_ngram}")
                self.spec_K = int(config.num_draft_tokens)
                self._spec_step = jax.jit(make_spec_decode_step(
                    cfg, self.dims, self.spec, self.spec_K, mesh=self.mesh,
                    dtype=dtype, ngram=config.spec_ngram,
                    part=self.partition),
                    static_argnames=("sample",), donate_argnums=(1,))
                # per-slot token history the in-graph drafter matches
                # against (prompt scattered at admission, accepted tokens
                # appended in-graph; -1 = unknown)
                self.dstate["hist"] = jnp.full(
                    (max_batch, max_seq_len), -1, jnp.int32)
        self._spec_drafted = 0
        self._spec_accepted = 0
        # mesh layout: place the decode state per the SAME specs the
        # whole-step shard_map uses (they must agree — kv_state_specs is
        # the single source of truth) and replicate the params; route
        # dirty-delta syncs through the ownership-masked sharded scatter
        if self.mesh is not None:
            specs = kv_state_specs(self.dstate, self.spec)
            self.dstate = {
                k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
                for k, v in self.dstate.items()}
            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, P()))
            self._scatter_delta = self._make_sharded_scatter()
        else:
            self._scatter_delta = _scatter_delta
        self.requests: Dict[int, Request] = {}      # registered, live
        self.finished: Dict[int, Request] = {}
        self._states: Dict[int, RequestState] = {}
        self._current: Optional[Request] = None     # mid-chunk prefill
        self._slot_of: Dict[int, int] = {}
        self._prefilling: Dict[int, int] = {}   # seq_id -> tokens installed
        self._pending_samp: List[Tuple[int, Request]] = []
        self._step_count = 0                    # scheduler clock (aging)
        # chunk trace: one ChunkRecord (seq_id, start, end, path,
        # fwd_tokens) per admitted chunk — scheduler tests pin the order,
        # the prefix-KV tests pin the per-chunk forward-token linearity
        self.admission_log: List[ChunkRecord] = []
        self._n_attn_layers = sum(cfg.attn_on_layer(l)
                                  for l in range(cfg.num_layers))
        self._has_recurrent = cfg.family in ("ssm", "hybrid")
        # host mirror of ctx_len: block-boundary checks must not read the
        # device array per request (that is one D2H sync per sequence)
        self._ctx_host = np.zeros(max_batch, np.int64)
        self._synced_full = False
        # ---- Utopia-native prefix cache (core/prefix_cache.py) ----------
        pc = config.prefix_cache
        reason = self._prefix_cache_unsupported()
        if pc is True and reason is not None:
            raise ValueError(f"prefix_cache=True is unsupported here: "
                             f"{reason}")
        self.prefix_cache: Optional[PrefixCache] = None
        if pc not in (None, False) and reason is None:
            self.prefix_cache = (pc if isinstance(pc, PrefixCache)
                                 else PrefixCache(self.manager))
        # per-request memo of the prompt's block chain hashes (computed
        # once, used by both the admission-time match and the
        # post-dispatch inserts)
        self._chain_cache: Dict[int, np.ndarray] = {}

    def _prefix_cache_unsupported(self) -> Optional[str]:
        """Why the prefix cache cannot run on this configuration (None =
        supported).  ``prefix_cache="auto"`` silently disables on these;
        ``prefix_cache=True`` raises with the reason."""
        if not self._n_attn_layers:
            return ("the model family has no attention KV blocks to "
                    "cache")
        if self.hybrid_cfg.mode == "restrictive_only":
            return ("content sharing needs a flexible segment (a "
                    "restrictive slot is tag-bound to a single vpn)")
        if self._front_tokens():
            return ("vlm frontend KV blocks precede the prompt blocks, "
                    "so prompt-block indices are not content-pure")
        return None

    # ------------------------------------------------------------ admission
    @property
    def waiting(self) -> Tuple[Request, ...]:
        """Requests whose prompt is not fully installed yet: the
        engine-owned mid-chunk request (if any) first, then the
        scheduler's queue."""
        head = (self._current,) if self._current is not None else ()
        return head + tuple(self.scheduler.pending())

    def has_unfinished(self) -> bool:
        return bool(self.waiting) or any(
            not self._states[sid].done for sid in self.requests)

    def submit(self, req: Request, share_prefix_from: Optional[int] = None,
               shared_blocks: int = 0) -> None:
        """Enqueue a request; ``step()`` admits it under the token budget
        in the order the configured scheduler decides.

        A ``seq_id`` may be reused once its previous request FINISHED;
        the engine then forgets the old incarnation entirely (its entry
        in ``finished`` and its ``stats()["per_request"]`` row are
        dropped).  Reusing a queued or live id raises."""
        bs = self.cfg.kv_block_size
        S = len(np.asarray(req.prompt))
        if S == 0:
            raise ValueError("empty prompt: an unadmittable request would "
                             "stall the queue head forever")
        if S % bs:
            raise ValueError(f"prompt length {S} must be a multiple of the "
                             f"KV block size {bs} (pad upstream)")
        front = self._front_tokens()
        if front % bs:
            raise ValueError(f"frontend length {front} must be a multiple "
                             f"of the KV block size {bs}")
        old = self._states.get(req.seq_id)
        if old is not None and not old.done:
            raise ValueError(f"seq_id {req.seq_id} is already queued or "
                             "live")
        if req.seq_id in self._slot_of:
            # finished but never released (auto_release=False): its slot,
            # ctx and KV blocks are still registered — re-admitting the
            # id would inherit them
            raise ValueError(
                f"seq_id {req.seq_id} finished but still holds its "
                f"sequence slot; call release({req.seq_id}) first or "
                "construct the engine with auto_release=True")
        if old is not None:
            # the old incarnation's telemetry row is about to be
            # dropped: bank its preempt/resume counts so the monotone
            # globals stay reconcilable (sum(rows) + dropped == global)
            self._dropped_preempts += old.preempts
            self._dropped_resumes += old.resumes
        self.finished.pop(req.seq_id, None)   # forget a finished reuse
        self._chain_cache.pop(req.seq_id, None)   # fresh chains on reuse
        if share_prefix_from is not None and shared_blocks:
            # legacy pairwise sharing: superseded by the automatic
            # content-addressed prefix cache — the source's prompt blocks
            # were published at its own admission, so the cache match at
            # THIS request's admission attaches the same physical slots
            # the explicit kwargs used to
            _warn_share_kwarg()
        now = time.perf_counter()
        state = RequestState(request=req, arrival=self._step_count,
                             submitted_at=now)
        if req.deadline_ms is not None:
            if req.deadline_ms < 0:
                raise ValueError(f"deadline_ms must be >= 0, got "
                                 f"{req.deadline_ms}")
            state.deadline_at = now + req.deadline_ms / 1e3
        object.__setattr__(req, "_engine_state", state)
        self._states[req.seq_id] = state
        self.scheduler.add(req, state.arrival)
        if self.metrics is not None:
            self.metrics.on_submit(req.seq_id, self._step_count)

    def add_request(self, req: Request,
                    share_prefix_from: Optional[int] = None,
                    shared_blocks: int = 0) -> int:
        """Legacy blocking admission: enqueue, then prefill the whole
        prompt immediately (draining anything queued ahead of it)."""
        self.submit(req, share_prefix_from, shared_blocks)
        pending = self._admit(budget=None)
        if any(r is req for r in self.waiting):   # could not even register
            raise PoolExhausted("no free sequence slot for blocking "
                                "add_request; release a sequence first")
        slot = self._slot_of[req.seq_id]   # before auto-release can free it
        host = jax.device_get({f"p{r.seq_id}": t for r, t in pending})
        for r, _ in pending:
            self._complete_prefill(r, int(host[f"p{r.seq_id}"]))
        return slot

    def _front_tokens(self) -> int:
        """Frontend tokens that occupy KV blocks (vlm image prefix; the
        audio frontend lives in the encoder, not the decoder cache)."""
        return self.cfg.frontend_tokens if self.cfg.family == "vlm" else 0

    def _admit(self, budget: Optional[int]
               ) -> List[Tuple[Request, jnp.ndarray]]:
        """Admit waiting prompts up to ``budget`` NEW tokens (None =
        unbounded), in scheduler order, chunked at KV-block granularity.

        Returns [(request, in-graph first-token array)] for every request
        whose FINAL chunk was installed this call; the caller folds the
        arrays into its single device fetch.
        """
        if self._current is None and not len(self.scheduler):
            return []
        m = self.manager
        bs = self.cfg.kv_block_size
        front = self._front_tokens()
        if budget is None:
            budget = sum(len(np.asarray(r.prompt)) for r in self.waiting)
        chunks: List[Tuple[Request, int, int, bool, bool]] = []
        # cache-hit regions attached at registration: extra hist spans
        # (the tail chunks never cover the attached prefix's tokens)
        hist_extra: List[Tuple[Request, int, int, bool, bool]] = []
        # exact capacity gating (ISSUE 6): every accepted chunk's
        # unmapped covering blocks are reserved against a dry-run ledger
        # BEFORE the chunk is committed, so the bucket allocations below
        # can never hit pool exhaustion mid-prefill.  ``reserved``
        # accumulates this round's not-yet-allocated vpns; each gate
        # replays them against a FRESH ledger (sharing/migration at
        # registration may have consumed flex slots since the last one).
        reserved: List[int] = []
        gate_alloc = (self._n_attn_layers > 0
                      and self.hybrid_cfg.mode != "restrictive_only")
        while budget >= bs:
            req = self._current
            if req is None:
                req = self.scheduler.select(self._step_count)
                if req is None:
                    break
                if req.seq_id in self._preempted:
                    # a preempted sequence re-entered through the queue:
                    # resume restores its saved blocks and rows, charging
                    # no prefill budget (nothing is re-forwarded)
                    with _span("engine.resume", seq_id=req.seq_id):
                        resumed = self._resume_preempted(req, reserved)
                    if not resumed:
                        break              # no slot / no capacity yet
                    if req.seq_id not in self._prefilling:
                        continue           # decode-live again this step
                    req = self._current    # mid-prefill: keep chunking
            if req.seq_id not in self._slot_of:
                if not m._free_seq_slots:
                    break                      # wait for a release
                if (self.overload_policy == "fail" and gate_alloc
                        and not self._footprint_admit(req)):
                    break          # fail-fast: serve only what fits whole
                slot = m.register_sequence(req.seq_id)
                self._slot_of[req.seq_id] = slot
                self.requests[req.seq_id] = req
                self._prefilling[req.seq_id] = 0
                self._pending_samp.append((slot, req))
                if self.prefix_cache is not None:
                    self._attach_cached_prefix(req, hist_extra)
            start = self._prefilling[req.seq_id]
            total = len(np.asarray(req.prompt))
            take = min(total - start, budget // bs * bs)
            if take <= 0:
                break
            end = start + take
            if gate_alloc:
                need = self._chunk_vpns(req, start, end, front)
                forced = (bool(need) and self._injector is not None
                          and self._injector.alloc_unavailable(
                              self._step_count, "admit"))
                if forced:
                    break      # injected transient denial: defer a step
                if need and not self._capacity_ok(reserved, need):
                    if (not reserved
                            and not self._others_hold_blocks(req.seq_id)):
                        # nothing else holds (or will hold) pool blocks,
                        # yet this prompt still does not fit: no amount
                        # of preemption can ever admit it
                        raise PoolExhausted(
                            f"request {req.seq_id}'s prompt alone "
                            "exceeds the KV pool and cannot be admitted",
                            **self._pool_diag())
                    st_in = self._states[req.seq_id]
                    if (self.overload_policy != "preempt"
                            or not self._make_room(
                                st_in, reserved, need,
                                exclude={c[0].seq_id for c in chunks}
                                | {req.seq_id})):
                        break          # defer: stay queued / mid-prefill
                reserved.extend(need)
            if self._current is None:
                # first chunk admitted: the engine owns the request until
                # its final chunk installs (a policy can reorder queued
                # requests, never interleave half-prefilled prompts)
                self.scheduler.pop(req)
                self._current = req
                st_q = self._states[req.seq_id]
                if st_q.admitted_at is None:
                    st_q.admitted_at = time.perf_counter()
            budget -= take
            self._prefilling[req.seq_id] = end
            final = end == total
            # chunk 0 has no prefix to read; later chunks consume the
            # installed prefix unless the oracle flag forces recompute
            use_prefix = self.prefill_mode == "prefix_kv" and start > 0
            chunks.append((req, start, end, final, use_prefix))
            self.admission_log.append(ChunkRecord(
                req.seq_id, start, end,
                "prefix_kv" if use_prefix else "recompute",
                (end - start) if use_prefix else front + end))
            if final:
                self._current = None
            # a partial chunk stays engine-owned with budget < bs, ending
            # the loop: it continues next step

        # newly registered sequences' SamplingParams must be on device
        # before any prefill dispatch samples its first token
        self._install_sampling()
        # ... and, under speculative decoding, so must their prompt
        # tokens: the in-graph drafter matches against the history —
        # including cache-attached prefixes, which no chunk ever covers
        self._install_hist(chunks + hist_extra)

        # ---- bucket by padded length; one dispatch per bucket -----------
        # Recompute chunks bucket by padded PREFIX length (the forward
        # redoes the whole prefix); prefix-KV chunks bucket by padded
        # CHUNK length (only the new tokens are forwarded).  Right padding
        # is exact under causal attention, and the recurrent (SSM/conv)
        # families pass per-row ``seq_len`` masks that zero dt past the
        # real length — pad positions become exact identity transitions —
        # so EVERY family shares the pow2 buckets (PR-2 bucketed ssm and
        # hybrid at exact lengths instead).
        pending: List[Tuple[Request, jnp.ndarray]] = []
        buckets: Dict[int, list] = defaultdict(list)
        pbuckets: Dict[Tuple[int, int], list] = defaultdict(list)
        for req, start, end, final, use_prefix in chunks:
            if use_prefix:
                take = end - start
                s_pad = bs * _next_pow2(take // bs)
                # the prefix read-buffer width must equal the padded KV
                # extent the recompute forward would pad THIS row to:
                # float reductions nest bitwise across pow2 tails but not
                # across arbitrary length pairs, so a shared max-width
                # buffer would break the bit-identical differential
                # contract (part of the bucket key, not a bucket max)
                nblk_buf = front // bs + _next_pow2(end // bs)
                pbuckets[(s_pad, nblk_buf)].append((req, start, end, final))
            else:
                s_pad = bs * _next_pow2(end // bs)
                buckets[s_pad].append((req, start, end, final))
        for s_pad, grp in sorted(buckets.items()):
            with _span("engine.prefill_dispatch"):
                pending.extend(self._prefill_bucket(grp, s_pad, front))
        for (s_pad, nblk_buf), grp in sorted(pbuckets.items()):
            with _span("engine.prefill_dispatch"):
                pending.extend(self._prefix_bucket(grp, s_pad, nblk_buf,
                                                   front))
        # ---- publish installed chunks to the prefix cache ---------------
        # POST-dispatch: entries become matchable from the NEXT admission
        # round onward, so a same-round duplicate can never attach a
        # block whose install dispatch has not run, and the pin
        # migrations' pending copies land with the step's normal
        # _apply_copies before anything reads the cached slots
        if self.prefix_cache is not None:
            for req, start, end, final, use_prefix in chunks:
                self._cache_insert_chunk(req, start, end)
        return pending

    # -------------------------------------------- overload / host KV tier
    def _chunk_vpns(self, req, start: int, end: int,
                    front: int) -> List[int]:
        """Vpns a prompt chunk's bucket allocation will actually fault in
        (unmapped covering blocks; mirrors _prefill_bucket/_prefix_bucket
        coverage exactly, including the frontend prefix on chunk 0)."""
        m = self.manager
        bs = self.cfg.kv_block_size
        s = m.seq_slot(req.seq_id)
        cb0 = (front + start) // bs if start else 0
        return [self.hybrid_cfg.vpn(s, cb)
                for cb in range(cb0, (front + end) // bs)
                if m.lookup(req.seq_id, cb)[0] < 0]

    # --------------------------------------------- prefix cache plumbing
    def _chains(self, req: Request) -> np.ndarray:
        """Memoized per-block chain hashes of a request's prompt."""
        c = self._chain_cache.get(req.seq_id)
        if c is None:
            c = block_hash_chain(np.asarray(req.prompt),
                                 self.cfg.kv_block_size)
            self._chain_cache[req.seq_id] = c
        return c

    def _attach_cached_prefix(self, req: Request, hist_extra) -> None:
        """Longest-cached-prefix match at registration: matched blocks
        attach read-only (the cache slot's refcount grows per attacher)
        and prefill starts at the tail.  The match is capped one block
        short of the full prompt so the FINAL chunk always runs — it
        produces the request's first-token logits."""
        pc = self.prefix_cache
        m = self.manager
        bs = self.cfg.kv_block_size
        pc.stats["lookups"] += 1
        prompt = np.asarray(req.prompt)
        entries = pc.match(prompt, self._chains(req))
        entries = entries[:len(prompt) // bs - 1]
        if not entries:
            return
        for cb, e in enumerate(entries):
            m.attach_cached_block(req.seq_id, cb, e.slot)
        matched = len(entries) * bs
        self._prefilling[req.seq_id] = matched
        st = self._states[req.seq_id]
        st.cached_blocks += len(entries)
        pc.stats["hits"] += 1
        pc.stats["dedup_blocks"] += len(entries)
        if self.spec_K:
            # the tail chunks never cover [0, matched): scatter the
            # attached prefix's tokens into the drafter history here
            hist_extra.append((req, 0, matched, False, False))

    def _cache_insert_chunk(self, req: Request, start: int,
                            end: int) -> None:
        """Publish a freshly installed chunk's blocks to the cache (one
        insert per covered block, parent-chained; dedup / full-set
        bypass handled inside :meth:`PrefixCache.insert`)."""
        bs = self.cfg.kv_block_size
        chains = self._chains(req)
        prompt = np.asarray(req.prompt)
        for cb in range(start // bs, end // bs):
            parent = CHAIN_SEED if cb == 0 else int(chains[cb - 1])
            self.prefix_cache.insert(
                int(chains[cb]), parent, prompt[cb * bs:(cb + 1) * bs],
                req.seq_id, cb)

    def _capacity_ok(self, reserved, need) -> bool:
        """Exact dry-run: could the pool allocate ``reserved`` (this
        round's already-accepted vpns) PLUS ``need`` right now?  A miss
        first reclaims UNREFERENCED prefix-cache entries — the cheapest
        rung of the degradation ladder: dropping clean cache frees one
        FlexSeg slot per entry and re-runs the dry-run against a fresh
        ledger — before the caller escalates to preemption."""
        want = list(reserved) + list(need)
        while True:
            if self.manager.alloc_ledger().reserve(want):
                return True
            if (self.prefix_cache is None
                    or not self.prefix_cache.evict_one()):
                return False

    def _others_hold_blocks(self, seq_id: int) -> bool:
        m = self.manager
        s = m.seq_slot(seq_id)
        nblk = self.hybrid_cfg.max_blocks_per_seq
        return any(vpn // nblk != s for vpn in m.blocks)

    def _pick_victim(self, exclude=frozenset()):
        """Choose a preemption victim via the scheduler's policy.

        Decode-live sequences are preferred over mid-prefill ones (a
        mid-prefill victim re-runs no work either way, but decode-live
        sequences hold full contexts — the policy gets the richer pool);
        finished-but-unreleased sequences are never victims (``release``
        is the tool for those).  Returns a RequestState or None."""
        decode, prefill = [], []
        for sid in self._slot_of:
            if sid in exclude:
                continue
            st = self._states.get(sid)
            if st is None or st.done:
                continue
            (prefill if sid in self._prefilling else decode).append(st)
        cands = decode or prefill
        if not cands:
            return None
        vic_fn = getattr(self.scheduler, "victim", None)
        if vic_fn is None:
            from .scheduler import default_victim as vic_fn
        return vic_fn(cands, self._step_count)

    def _make_room(self, incoming_st, reserved, need, exclude) -> bool:
        """Preempt policy-approved victims until ``reserved + need``
        fits.  ``should_preempt`` gates every eviction (FIFO/SPF always
        say no — admission waits; priority lets a strictly
        higher-effective request evict), so this can only loop as long
        as victims keep being approved, and each preemption removes one
        candidate."""
        while not self._capacity_ok(reserved, need):
            vic = self._pick_victim(exclude)
            if vic is None:
                return False
            sp = getattr(self.scheduler, "should_preempt", None)
            if sp is None or not sp(incoming_st.request,
                                    incoming_st.arrival, vic,
                                    self._step_count):
                return False
            self.preempt_request(vic.request.seq_id)
        return True

    def _footprint_blocks(self, req) -> int:
        """Whole-request KV footprint in blocks (prompt + frontend + all
        of max_new_tokens, plus one spare block for a speculative window
        overshoot), clamped to the per-sequence maximum."""
        bs = self.cfg.kv_block_size
        total = (self._front_tokens() + len(np.asarray(req.prompt))
                 + req.max_new_tokens)
        need = (total + bs - 1) // bs + (1 if self.spec_K else 0)
        return min(need, self.spec.max_blocks_per_seq)

    def _footprint_admit(self, req) -> bool:
        """Fail-fast admission gate: admit only when the request's FULL
        footprint fits next to every resident sequence's — "serve only
        what fits", the PR-5 behaviour made explicit.  Raises for a
        request whose footprint alone exceeds the pool."""
        m = self.manager
        need = self._footprint_blocks(req)
        cap = self.hybrid_cfg.total_slots
        if need > cap:
            raise PoolExhausted(
                f"request {req.seq_id} needs {need} KV blocks but the "
                f"pool only has {cap}", **self._pool_diag())
        held = 0
        nblk = self.spec.max_blocks_per_seq
        for sid in self._slot_of:
            st = self._states[sid]
            if st.done:        # finished-unreleased: count actual blocks
                held += sum(1 for b in range(nblk)
                            if m.lookup(sid, b)[0] >= 0)
            else:
                held += self._footprint_blocks(st.request)
        return held + need <= cap

    def _pool_diag(self) -> Dict[str, int]:
        """Structured occupancy diagnostics attached to PoolExhausted."""
        m = self.manager
        return dict(
            pool_blocks=self.hybrid_cfg.total_slots,
            mapped_blocks=sum(1 for i in m.blocks.values() if i.slot >= 0),
            free_flex=len(m.flex_free),
            queued=len(self.waiting),
            live=sum(1 for sid in self.requests
                     if not self._states[sid].done),
            finished_unreleased=sum(1 for sid in self._slot_of
                                    if self._states[sid].done),
            preempted=len(self._preempted))

    def _attribute_swap(self, counter: np.ndarray, rec, slots) -> None:
        """Split a swap record's bytes across shards so the per-shard
        counters sum EXACTLY to the global one: KV bytes go to each
        block's owning shard (equal share per block — blocks are
        uniform), everything else (recurrent/cross rows, spec history)
        is replicated state and is charged to shard 0."""
        kv_bytes = 0 if rec.kv is None else int(np.asarray(rec.kv).nbytes)
        if kv_bytes and slots:
            per, extra = divmod(kv_bytes, len(slots))
            for i, s in enumerate(slots):
                owner = (self.partition.shard_of_slot(int(s))
                         if self.partition is not None else 0)
                counter[owner] += per + (extra if i == 0 else 0)
        counter[0] += rec.nbytes - kv_bytes

    def preempt_request(self, seq_id: int) -> None:
        """Swap a live sequence out to the host KV tier (ISSUE 6).

        Safe points only: between steps, or inside ``step()`` before
        admission / after the commit (the injector's "pre"/"post"
        phases) — never between a dispatch and its fetch.  Everything
        needed to continue bit-identically is captured in ONE batched
        ``device_get``: the mapped pool blocks (KV), the recurrent
        (ssm/conv) and cross-attention rows, the spec history row and
        the committed context.  Sampling keys need no save — they derive
        from (seed, seq_id) and fold the absolute position, so a resumed
        request samples exactly what it would have uninterrupted.  The
        request re-enters the scheduler queue with its ORIGINAL arrival
        step, so aging policies keep its seniority."""
        st = self._states.get(seq_id)
        if st is None or st.done or seq_id not in self._slot_of:
            raise ValueError(f"sequence {seq_id} is not live")
        with _span("engine.preempt", seq_id=seq_id):
            self._swap_out(seq_id, st)

    def _swap_out(self, seq_id: int, st: RequestState) -> None:
        m = self.manager
        slot = self._slot_of[seq_id]
        # pending migration copies must land BEFORE the gather: the
        # manager's slot map is post-copy, the pool data may not be yet
        self._apply_copies()
        fetch: Dict[str, Any] = {}
        mapped: List[int] = []
        if self._n_attn_layers:
            for b in range(self.spec.max_blocks_per_seq):
                bslot, _ = m.lookup(seq_id, b)
                if bslot >= 0:
                    mapped.append(bslot)
            if mapped:
                mp = np.asarray(mapped, np.int32)
                if self.partition is not None:
                    mp = self.partition.phys(mp)
                sl = jnp.asarray(mp)
                fetch["kv"] = jnp.stack([self.dstate["k_pool"][:, sl],
                                         self.dstate["v_pool"][:, sl]])
        for key in ("ssm", "conv", "cross_k", "cross_v"):
            if key in self.dstate:
                fetch[key] = self.dstate[key][:, slot]
        if self.spec_K:
            fetch["hist"] = self.dstate["hist"][slot]
        host = jax.device_get(fetch) if fetch else {}
        saved = m.preempt(seq_id)
        assert len(saved) == len(mapped), "gather/release block mismatch"
        rec = _HostTierSeq(
            seq_id=seq_id, ctx=int(self._ctx_host[slot]),
            prefill_progress=self._prefilling.get(seq_id),
            blocks=saved, kv=host.get("kv"),
            rows={k: v for k, v in host.items() if k != "kv"},
            nbytes=sum(np.asarray(v).nbytes for v in host.values()))
        # engine-side slot teardown (release() minus the finishing)
        del self._slot_of[seq_id]
        self.dstate["ctx_len"] = self.dstate["ctx_len"].at[slot].set(0)
        self._ctx_host[slot] = 0
        if self.spec_K:
            self.dstate["hist"] = self.dstate["hist"].at[slot].set(-1)
        req = self.requests.pop(seq_id)
        self._prefilling.pop(seq_id, None)
        if self._current is not None and self._current.seq_id == seq_id:
            self._current = None
        self._pending_samp = [(s, r) for s, r in self._pending_samp
                              if r.seq_id != seq_id]
        self._preempted[seq_id] = rec
        self._swap_bytes_out += rec.nbytes
        self._attribute_swap(self._shard_swap_out, rec, mapped)
        st.preempts += 1
        self._request_preempts += 1
        self._progress_events += 1
        self.scheduler.add(req, st.arrival)
        self._sync_translation()

    def _resume_preempted(self, req: Request, reserved) -> bool:
        """Bring a preempted sequence back from the host tier: fresh
        sequence slot, fresh pool slots (capacity-gated against the
        ledger, preempting policy-approved victims if needed), saved KV
        scattered back, rows and context restored, sampling re-scattered.
        A mid-prefill victim becomes the engine-owned chunk request again
        and continues through the normal prefix-KV chunk path.  Returns
        False — leaving the request queued — when no sequence slot or
        capacity is available yet."""
        m = self.manager
        sid = req.seq_id
        rec = self._preempted[sid]
        st = self._states[sid]
        if not m._free_seq_slots:
            return False
        if (self._injector is not None
                and self._injector.alloc_unavailable(self._step_count,
                                                     "resume")):
            return False
        trial = m._free_seq_slots[-1]    # the slot register_sequence pops
        vpns = [self.hybrid_cfg.vpn(trial, b) for b, _ in rec.blocks]
        if not self._capacity_ok(reserved, vpns):
            if (self.overload_policy != "preempt"
                    or not self._make_room(st, reserved, vpns,
                                           exclude={sid})):
                return False
        self.scheduler.pop(req)
        slot = m.register_sequence(sid)
        m.resume(sid, rec.blocks)
        self._apply_copies()        # resume-time evictions land first
        if rec.kv is not None:
            # re-resolve AFTER the copies: a later block's allocation may
            # have evict-migrated an earlier one within this same resume,
            # so the scatter must target where each block lives now
            self._write_pool(
                np.asarray([m.lookup(sid, b)[0] for b, _ in rec.blocks],
                           np.int32), kv=rec.kv)
        for key, row in rec.rows.items():
            if key == "hist":
                self.dstate["hist"] = \
                    self.dstate["hist"].at[slot].set(jnp.asarray(row))
            else:
                self.dstate[key] = \
                    self.dstate[key].at[:, slot].set(jnp.asarray(row))
        self.dstate["ctx_len"] = \
            self.dstate["ctx_len"].at[slot].set(rec.ctx)
        self._ctx_host[slot] = rec.ctx
        self._slot_of[sid] = slot
        self.requests[sid] = req
        self._pending_samp.append((slot, req))
        if rec.prefill_progress is not None:
            self._prefilling[sid] = rec.prefill_progress
            self._current = req
        del self._preempted[sid]
        self._swap_bytes_in += rec.nbytes
        self._attribute_swap(
            self._shard_swap_in, rec,
            [m.lookup(sid, b)[0] for b, _ in rec.blocks])
        st.last_step = self._step_count
        st.resumes += 1
        self._request_resumes += 1
        self._progress_events += 1
        return True

    def _run_forced_preempts(self, targets) -> None:
        """Apply the injector's forced preemptions; ``"auto"`` targets
        resolve through the victim policy, invalid/finished targets are
        skipped (the schedule may outlive the sequence it named)."""
        for t in targets:
            if t == "auto" or t is None:
                vic = self._pick_victim()
                sid = None if vic is None else vic.request.seq_id
            else:
                sid = int(t)
            st = self._states.get(sid) if sid is not None else None
            if (st is None or st.done or sid not in self._slot_of
                    or self.hybrid_cfg.mode == "restrictive_only"):
                continue
            self.preempt_request(sid)

    def _ensure_decode_blocks(self, st: RequestState) -> None:
        """Map every block the next decode dispatch will write for
        ``st`` (the boundary block, or the whole [pos, pos+K] window
        under speculation).

        Hybrid/flexible: a capacity miss walks the degradation ladder —
        preempt a policy-chosen victim and retry — instead of the
        pre-overload SWAP fall-through, where a SWAP'd current block
        made ``w_valid`` mask the KV write: the token stream kept going
        but the cache entry was silently dropped.  Under
        ``overload_policy="fail"`` the miss raises ``PoolExhausted``
        with occupancy diagnostics.  ``restrictive_only`` keeps the
        legacy per-block swap_in path bit-for-bit (set conflicts swap by
        design, Fig. 9)."""
        m = self.manager
        bs = self.cfg.kv_block_size
        K = self.spec_K
        nblk = self.spec.max_blocks_per_seq
        sid = st.request.seq_id
        pos = int(self._ctx_host[self._slot_of[sid]])
        if K:
            blocks = range(pos // bs, min((pos + K) // bs, nblk - 1) + 1)
        elif pos % bs == 0:
            blocks = (pos // bs,)
        else:
            return
        restrictive = self.hybrid_cfg.mode == "restrictive_only"
        for b in blocks:
            bslot, seg = m.lookup(sid, b)
            if bslot >= 0:
                continue
            if restrictive:
                info = m.allocate_block(sid, b)
                if info.seg == SWAP:
                    m.swap_in(sid, b)
                    st.swap_faults += 1
                continue
            in_swap = seg == SWAP     # legacy per-block swap bookkeeping
            vpn = self.hybrid_cfg.vpn(m.seq_slot(sid), b)
            first = True
            while True:
                forced = (first and self._injector is not None
                          and self._injector.alloc_unavailable(
                              self._step_count, "decode"))
                first = False
                if not forced and self._capacity_ok((), (vpn,)):
                    if in_swap:
                        m.swap_in(sid, b)
                        st.swap_faults += 1
                    else:
                        m.allocate_block(sid, b)
                    break
                if self.overload_policy != "preempt":
                    raise PoolExhausted(
                        f"decode step cannot allocate a KV block for "
                        f"sequence {sid}", **self._pool_diag())
                vic = self._pick_victim(exclude={sid})
                if vic is None:
                    raise PoolExhausted(
                        f"sequence {sid} cannot hold its own KV blocks "
                        "and nothing is left to preempt",
                        **self._pool_diag())
                self.preempt_request(vic.request.seq_id)

    def _install_sampling(self) -> None:
        """Scatter newly registered requests' SamplingParams into the
        per-slot device arrays (4 pow2-padded scatters; admission path
        only — the steady-state step never touches these)."""
        if not self._pending_samp:
            return
        rows = np.asarray([s for s, _ in self._pending_samp], np.int32)
        sp = [r.sampling for _, r in self._pending_samp]
        keys = np.stack([prng_key_data(p, r.seq_id)
                         for p, (_, r) in zip(sp, self._pending_samp)])
        self._pending_samp.clear()
        n = _next_pow2(rows.size)

        def pad(a):
            reps = n - a.shape[0]
            if reps:
                a = np.concatenate([a, np.repeat(a[:1], reps, axis=0)])
            return a

        # duplicate scatter index with duplicated value — benign
        ji = jnp.asarray(pad(rows))
        self.dstate["samp_temp"] = self.dstate["samp_temp"].at[ji].set(
            jnp.asarray(pad(np.asarray([p.temperature for p in sp],
                                       np.float32))))
        self.dstate["samp_topk"] = self.dstate["samp_topk"].at[ji].set(
            jnp.asarray(pad(np.asarray([p.top_k for p in sp], np.int32))))
        self.dstate["samp_topp"] = self.dstate["samp_topp"].at[ji].set(
            jnp.asarray(pad(np.asarray([p.top_p for p in sp], np.float32))))
        self.dstate["samp_key"] = self.dstate["samp_key"].at[ji].set(
            jnp.asarray(pad(keys.astype(np.uint32))))

    def _install_hist(self, chunks) -> None:
        """Scatter admitted prompt chunks into the per-slot token history
        the in-graph drafter matches against (ONE pow2-padded flat
        scatter per admission call; steady-state decode steps append
        accepted tokens in-graph and never touch this path).  Frontend
        (vlm) positions stay -1 — no token ever matches them."""
        if not self.spec_K or not chunks:
            return
        H = self.dstate["hist"].shape[1]
        front = self._front_tokens()
        idxs, vals = [], []
        for req, start, end, final, use_prefix in chunks:
            slot = self._slot_of[req.seq_id]
            base = slot * H + front + start
            idxs.append(np.arange(base, base + (end - start), dtype=np.int64))
            vals.append(np.asarray(req.prompt[start:end], np.int32))
        idx = np.concatenate(idxs)
        val = np.concatenate(vals)
        # pad to pow2 with an out-of-bounds index (dropped): bounded
        # scatter shapes, same discipline as the dirty-delta syncs
        idx = _pad_pow2(idx, self.max_batch * H)
        val = _pad_pow2(val, 0)
        self.dstate["hist"] = self.dstate["hist"].reshape(-1).at[
            jnp.asarray(idx)].set(jnp.asarray(val),
                                  mode="drop").reshape(self.max_batch, H)

    def _prefill_bucket(self, grp, s_pad: int, front: int):
        """Allocate blocks and run ONE batched prefill dispatch for a
        bucket of same-padded-length chunks."""
        m = self.manager
        bs = self.cfg.kv_block_size
        B_pad = _next_pow2(len(grp))
        nblk_cache = (front + s_pad) // bs
        tokens = np.zeros((B_pad, s_pad), np.int64)
        slots = -np.ones((B_pad, nblk_cache), np.int32)
        slot_ids = np.full(B_pad, -1, np.int32)
        ctx = np.zeros(B_pad, np.int32)
        last_pos = np.zeros(B_pad, np.int32)
        allocated: List[Tuple[int, int, int]] = []
        frontend = None
        if self.cfg.frontend != "none":
            frontend = np.zeros((B_pad, self.cfg.frontend_tokens,
                                 self.cfg.d_model), np.float32)
        for i, (req, start, end, final) in enumerate(grp):
            prompt = np.asarray(req.prompt)
            tokens[i, :end] = prompt[:end]
            slot_ids[i] = self._slot_of[req.seq_id]
            ctx[i] = end + front
            last_pos[i] = end - 1
            if frontend is not None:
                frontend[i] = req.frontend
            # new cache blocks this chunk (the first chunk also covers the
            # frontend prefix); blocks already mapped — earlier chunks,
            # shared prefix — install nothing.  Attention-free families
            # have no KV blocks to translate (DESIGN.md
            # §Arch-applicability), so nothing is allocated either.
            if not self._n_attn_layers:
                continue
            cb0 = (front + start) // bs if start else 0
            for cb in range(cb0, (front + end) // bs):
                if m.lookup(req.seq_id, cb)[0] >= 0:
                    continue
                info = m.allocate_block(req.seq_id, cb)
                if info.seg == SWAP:
                    raise RuntimeError("pool exhausted during prefill")
                allocated.append((i, req.seq_id, cb))
        # allocation-time evictions queued copies: drain before the
        # scatter, then RE-resolve every slot — a later allocation in
        # this same loop may have evict-migrated an earlier one, and the
        # scatter must write where the block lives NOW, not where it was
        # first placed (under a tight pool the stale slot already
        # belongs to another block)
        self._apply_copies()
        for i, sid, cb in allocated:
            slots[i, cb] = m.lookup(sid, cb)[0]
        batch = {"tokens": jnp.asarray(tokens)}
        if self._has_recurrent:
            # per-row real lengths: dt is zeroed past them, so the pow2
            # pad tail is an exact identity transition of the SSM state
            batch["seq_len"] = jnp.asarray(ctx - front)
        if frontend is not None:
            batch["frontend"] = jnp.asarray(frontend)
        any_sampled = any(not req.sampling.is_greedy
                          for req, _, _, _ in grp)
        _, self.dstate, pstats = self._prefill_step(
            self.params, self.dstate, batch, jnp.asarray(slots),
            jnp.asarray(slot_ids), jnp.asarray(ctx), jnp.asarray(last_pos),
            sample=any_sampled)
        if "moe" in pstats:
            self._moe_pending.append(pstats["moe"])
        out = []
        for i, (req, start, end, final) in enumerate(grp):
            self._ctx_host[slot_ids[i]] = int(ctx[i])
            if final:
                out.append((req, pstats["next_token"][i]))
        return out

    def _prefix_bucket(self, grp, s_pad: int, nblk_buf: int, front: int):
        """ONE batched prefix-KV dispatch for a bucket of same-shaped
        chunks: allocate the chunks' new blocks, then forward ONLY the
        chunk tokens, attending over the prefix's installed pool blocks
        (gathered via the translated slots) — linear chunk cost.

        ``nblk_buf`` (part of the bucket key) is each row's padded KV
        extent in blocks, chosen in ``_admit`` to match what the
        recompute forward would pad the same row to — the bit-identity
        contract of the differential oracle suite."""
        m = self.manager
        bs = self.cfg.kv_block_size
        B_pad = _next_pow2(len(grp))
        nblk_chunk = s_pad // bs
        tokens = np.zeros((B_pad, s_pad), np.int64)
        new_slots = -np.ones((B_pad, nblk_chunk), np.int32)
        prefix_slots = -np.ones((B_pad, nblk_buf), np.int32)
        slot_ids = np.full(B_pad, -1, np.int32)
        ctx = np.zeros(B_pad, np.int32)
        pctx = np.zeros(B_pad, np.int32)
        last_pos = np.zeros(B_pad, np.int32)
        allocated: List[Tuple[int, int, int, int]] = []
        for i, (req, start, end, final) in enumerate(grp):
            prompt = np.asarray(req.prompt)
            take = end - start
            tokens[i, :take] = prompt[start:end]
            slot_ids[i] = self._slot_of[req.seq_id]
            ctx[i] = end + front
            pctx[i] = start + front
            last_pos[i] = take - 1
            if not self._n_attn_layers:
                continue
            start_blk = (front + start) // bs
            for j, cb in enumerate(range(start_blk, (front + end) // bs)):
                if m.lookup(req.seq_id, cb)[0] >= 0:
                    continue      # shared-prefix block: already installed
                info = m.allocate_block(req.seq_id, cb)
                if info.seg == SWAP:
                    raise RuntimeError("pool exhausted during prefill")
                allocated.append((i, j, req.seq_id, cb))
        # allocation-time evictions queue slot migrations: drain them
        # BEFORE reading the prefix slots so the gather below sees the
        # post-copy pool layout — and re-resolve the NEW slots too: a
        # later allocation in the loop above may have evict-migrated an
        # earlier one, so the write slot captured at allocation time can
        # be stale (it already belongs to the evicting block)
        self._apply_copies()
        for i, j, sid, cb in allocated:
            new_slots[i, j] = m.lookup(sid, cb)[0]
        if self._n_attn_layers:
            for i, (req, start, end, final) in enumerate(grp):
                for cb in range((front + start) // bs):
                    slot, _ = m.lookup(req.seq_id, cb)
                    if slot < 0:
                        # a prefix block was evicted to swap: its data is
                        # gone and the prefix-KV read cannot rebuild it
                        raise RuntimeError(
                            "prefix block swapped out during chunked "
                            "prefill; grow the pool or use "
                            "prefill_mode='recompute'")
                    prefix_slots[i, cb] = slot
        any_sampled = any(not req.sampling.is_greedy
                          for req, _, _, _ in grp)
        _, self.dstate, pstats = self._prefix_step(
            self.params, self.dstate, {"tokens": jnp.asarray(tokens)},
            jnp.asarray(new_slots), jnp.asarray(prefix_slots),
            jnp.asarray(slot_ids), jnp.asarray(ctx), jnp.asarray(pctx),
            jnp.asarray(last_pos), sample=any_sampled)
        if "moe" in pstats:
            self._moe_pending.append(pstats["moe"])
        out = []
        for i, (req, start, end, final) in enumerate(grp):
            self._ctx_host[slot_ids[i]] = int(ctx[i])
            if final:
                out.append((req, pstats["next_token"][i]))
        return out

    def _complete_prefill(self, req: Request, nxt: int) -> None:
        self._prefilling.pop(req.seq_id, None)
        st = self._states[req.seq_id]
        st.generated.append(nxt)
        st.new_tokens.append(nxt)
        self._tokens_emitted += 1
        self._maybe_finish(st, nxt)

    def _finish(self, st: RequestState, reason: str) -> None:
        st.done = True
        st.finish_reason = reason
        if self.auto_release and st.request.seq_id in self._slot_of:
            self.release(st.request.seq_id)
        if self.metrics is not None:
            self.metrics.on_finish(st.request.seq_id, self._step_count,
                                   len(st.generated), reason)

    def _maybe_finish(self, st: RequestState, nxt: int) -> None:
        if st.done:
            return
        req = st.request
        hit_eos = req.eos_token is not None and nxt == req.eos_token
        if hit_eos or len(st.generated) >= req.max_new_tokens:
            self._finish(st, "stop" if hit_eos else "length")

    # ------------------------------------------------------------- serving
    def _make_sharded_scatter(self):
        """Build the mesh twin of ``_scatter_delta``: one jitted
        shard_map in which each shard keeps ONLY the delta entries whose
        set index (resp. flex vpn) falls in its own range, rebases them,
        and drops the rest out of bounds — dirty deltas are routed to the
        owning shard and nowhere else (DESIGN.md §sharded-serving).  The
        caller's out-of-bounds sentinels (padded device sizes) fall
        outside every shard's range, so an empty delta side still costs
        one dropped row, exactly like the local path."""
        part, spec = self.partition, self.spec
        spm, vpm = part.sets_per_shard, part.vpns_per_shard
        ma = spec.model_axis

        def local(tar, sf, flex, sets_idx, tar_rows, sf_rows, flex_idx,
                  flex_vals):
            mi = jax.lax.axis_index(ma)
            lo = (mi * spm).astype(sets_idx.dtype)
            si = jnp.where((sets_idx >= lo) & (sets_idx < lo + spm),
                           sets_idx - lo, spm)
            tar = tar.at[0, si].set(tar_rows, mode="drop")
            sf = sf.at[0, si].set(sf_rows, mode="drop")
            flo = (mi * vpm).astype(flex_idx.dtype)
            fi = jnp.where((flex_idx >= flo) & (flex_idx < flo + vpm),
                           flex_idx - flo, vpm)
            flex = flex.at[0, fi].set(flex_vals, mode="drop")
            return tar, sf, flex

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(None, ma, None), P(None, ma), P(None, ma),
                      P(), P(), P(), P(), P()),
            out_specs=(P(None, ma, None), P(None, ma), P(None, ma)),
            check_vma=False)
        return jax.jit(fn)

    def _sync_translation(self, full: bool = False) -> None:
        """Upload TAR/SF/flex changes.

        First call (or ``full=True``) uploads everything; afterwards only
        the entries dirtied since the previous sync are scattered — three
        bounded-size dispatches instead of re-streaming the whole tables.
        Under the mesh layout the full upload builds the PADDED mirrors
        (zero TAR rows / -1 flex entries past the real sizes) and places
        them with the state's shardings; deltas go through the
        ownership-routed sharded scatter.
        """
        m = self.manager
        if full or not self._synced_full:
            m.take_dirty()             # everything is covered below
            part = self.partition
            if part is None:
                self.dstate["tar"] = jnp.asarray(m.tar)[None]
                self.dstate["sf"] = jnp.asarray(m.sf)[None]
                self.dstate["flex"] = jnp.asarray(
                    m.flex_table.reshape(-1))[None]
            else:
                tar_h = np.zeros((part.n_sets_padded,) + m.tar.shape[1:],
                                 m.tar.dtype)
                tar_h[:m.tar.shape[0]] = m.tar
                sf_h = np.zeros(part.n_sets_padded, m.sf.dtype)
                sf_h[:m.sf.shape[0]] = m.sf
                flat = m.flex_table.reshape(-1)
                flex_h = np.full(part.vpn_padded, -1, flat.dtype)
                flex_h[:flat.size] = flat
                specs = kv_state_specs(self.dstate, self.spec)
                put = lambda k, a: jax.device_put(
                    a, NamedSharding(self.mesh, specs[k]))
                self.dstate["tar"] = put("tar", tar_h[None])
                self.dstate["sf"] = put("sf", sf_h[None])
                self.dstate["flex"] = put("flex", flex_h[None])
            self._synced_full = True
            return
        sets, flex_idx = m.take_dirty()
        if not sets.size and not flex_idx.size:
            return
        # pad to pow2 with a duplicate index (same value — benign); an
        # empty side passes one out-of-bounds sentinel row that the
        # jitted scatter drops.  ONE dispatch applies the whole delta.
        if sets.size:
            sets = _pad_pow2(sets, sets[0])
            tar_rows, sf_rows = m.tar[sets], m.sf[sets]
        else:
            sets = np.asarray([m.tar.shape[0]], np.int64)
            tar_rows = np.zeros((1,) + m.tar.shape[1:], m.tar.dtype)
            sf_rows = np.zeros(1, m.sf.dtype)
        flat = m.flex_table.reshape(-1)
        if flex_idx.size:
            flex_idx = _pad_pow2(flex_idx, flex_idx[0])
            flex_vals = flat[flex_idx]
        else:
            flex_idx = np.asarray([flat.size], np.int64)
            flex_vals = np.zeros(1, flat.dtype)
        if self.partition is not None:
            # sentinels must be out of bounds for the PADDED device
            # tables: the unpadded flex size can alias a padded position
            # (which must stay -1) and must not be written.
            sets = np.where(sets == m.tar.shape[0],
                            self.dstate["tar"].shape[1], sets)
            flex_idx = np.where(flex_idx == flat.size,
                                self.dstate["flex"].shape[1], flex_idx)
        self.dstate["tar"], self.dstate["sf"], self.dstate["flex"] = \
            self._scatter_delta(
                self.dstate["tar"], self.dstate["sf"], self.dstate["flex"],
                jnp.asarray(sets), jnp.asarray(tar_rows),
                jnp.asarray(sf_rows), jnp.asarray(flex_idx),
                jnp.asarray(flex_vals))

    def _apply_copies(self) -> None:
        """Apply pending slot migrations as ONE gather/scatter per pool.

        Chains inside a drain (a->b, b->c) are resolved host-side to the
        original source so the batched gather reads pre-copy contents with
        sequential semantics.
        """
        copies = self.manager.take_pending_copies()
        if not copies:
            return
        root: Dict[int, int] = {}
        for src, dst in copies:
            root[dst] = root.get(src, src)
        pairs = [(d, s) for d, s in root.items() if d != s]
        if not pairs:
            return
        self._write_pool(np.asarray([d for d, _ in pairs], np.int32),
                         src=np.asarray([s for _, s in pairs], np.int32))

    def _write_pool(self, dst: np.ndarray, src: Optional[np.ndarray] = None,
                    kv: Optional[np.ndarray] = None) -> None:
        """Write logical pool slots ``dst`` of both KV pools in place
        (``_write_pool_slots``): copies from slots ``src``, or the host
        blocks ``kv`` of shape ``(2, L, len(dst), ...)``."""
        n = dst.size
        self._pool_writes["dispatches"] += 1
        self._pool_writes["slots"] += n
        # pad to pow2 by duplicating the first entry (duplicate scatter
        # index with the same value — benign): bounded scatter shapes
        dst = _pad_pow2(dst, dst[0])
        if src is not None:
            src = _pad_pow2(src, src[0])
        if kv is not None and dst.size > n:
            kv = np.concatenate(
                [kv, np.repeat(kv[:, :, :1], dst.size - n, axis=2)], axis=2)
        if self.partition is not None:
            # manager slots are logical; the sharded pool is laid out in
            # shard-contiguous physical order.  GSPMD turns this into the
            # exact cross-shard data movement.
            dst = self.partition.phys(dst)
            if src is not None:
                src = self.partition.phys(src)
        self.dstate["k_pool"], self.dstate["v_pool"] = _write_pool_slots(
            self.dstate["k_pool"], self.dstate["v_pool"], jnp.asarray(dst),
            src=None if src is None else jnp.asarray(src),
            kv=None if kv is None else jnp.asarray(kv))

    def step(self) -> Dict[int, int]:
        """One engine step: admit under the prefill budget, then decode
        all live sequences.  Returns {seq_id: token} for every sequence
        that produced a token (prefill completions AND decodes).

        With speculative decoding a step can commit SEVERAL tokens per
        sequence; the returned value is the LAST token committed this
        step (the scalar contract preserved for direct-step drivers).
        Consume the full stream through ``poll()`` / ``stream()`` —
        their ``RequestOutput.new_token_ids`` carry every committed
        token — or ``Request.generated``.

        With a ``MetricsLogger`` attached (``EngineConfig.metrics``)
        each step additionally emits one host-side event — wall time on
        the monotonic clock, counter deltas, occupancy gauges — after
        the commit.  The logger path performs no device operation, so
        logger-on streams are bit-identical to logger-off."""
        if self.metrics is None:
            with _span("engine.step"):
                return self._step_impl()
        t0 = time.perf_counter()
        with _span("engine.step"):
            out = self._step_impl()
        wall = time.perf_counter() - t0
        self.metrics.on_step(self._step_count, wall,
                             self._metrics_counters(),
                             self._metrics_gauges())
        return out

    def _step_impl(self) -> Dict[int, int]:
        self._step_count += 1
        if self._injector is not None:
            # crash point "pre": the step boundary BEFORE this step
            # mutated anything — a scheduled InjectedStepFault simulates
            # the process dying here; recovery is restore-from-snapshot
            # (runtime/resilient_serve.py), never unwinding
            crash = getattr(self._injector, "maybe_crash", None)
            if crash is not None:
                crash(self._step_count, "pre")
            # safe point #1: before admission — a forced "pre" preempt
            # tears a victim out between prompt chunks / decode steps
            self._run_forced_preempts(
                self._injector.forced_preempts(self._step_count, "pre"))
        fetch = {}
        with _span("engine.admit"):
            pending = self._admit(self.prefill_budget)
        for r, tok in pending:
            fetch[f"p{r.seq_id}"] = tok
        if self._moe_pending:
            fetch["moe_prefill"] = self._moe_pending
            self._moe_pending = []
        live = [self._states[sid] for sid, r in self.requests.items()
                if not self._states[sid].done
                and sid not in self._prefilling]
        K = self.spec_K
        if live and self._n_attn_layers:
            # map the blocks this dispatch will write FIRST: an
            # allocation miss may preempt another live sequence (it then
            # drops out of the batch below), so tokens/active are built
            # only after residency settles
            with _span("engine.ensure_blocks"):
                for st in live:
                    if st.request.seq_id in self._slot_of:
                        self._ensure_decode_blocks(st)
            live = [st for st in live
                    if st.request.seq_id in self._slot_of]
        if live:
            # gather last tokens — all from host state, no device reads
            tokens = np.zeros(self.max_batch, np.int64)
            active = np.zeros(self.max_batch, bool)
            for st in live:
                slot = self._slot_of[st.request.seq_id]
                active[slot] = True
                tokens[slot] = st.generated[-1]
            with _span("engine.apply_copies"):
                self._apply_copies()
            with _span("engine.sync_translation"):
                self._sync_translation()
            # pre-step context snapshot: the telemetry mask below must
            # count the blocks that existed when the step TRANSLATED, and
            # the boundary block only if its allocation actually mapped
            ctx_pre = self._ctx_host.copy()

            any_sampled = any(not st.request.sampling.is_greedy
                              for st in live)
            step_fn = self._spec_step if K else self._serve_step
            with _span("engine.dispatch"):
                logits, self.dstate, tstats = step_fn(
                    self.params, self.dstate, jnp.asarray(tokens),
                    jnp.asarray(active), sample=any_sampled)

            if K:
                # (B, K+1) window tokens + per-slot emitted counts ride
                # the same single fetch the scalar path uses
                fetch["next"] = tstats["acc_tokens"]
                fetch["n_emit"] = tstats["n_emit"]
            else:
                fetch["next"] = tstats["next_token"]
            fetch["ctx"] = self.dstate["ctx_len"]
            if "moe" in tstats:
                fetch["moe_decode"] = tstats["moe"]
            want_stats = self._n_attn_layers and self.track_stats
            if want_stats:
                fetch["in_rest"] = tstats["in_rest"]
                fetch["accesses"] = tstats["accesses"]
                fetch["mapped"] = tstats["mapped"]

        if not fetch:
            if self._injector is not None:
                self._run_forced_preempts(
                    self._injector.forced_preempts(self._step_count,
                                                   "post"))
                crash = getattr(self._injector, "maybe_crash", None)
                if crash is not None:
                    crash(self._step_count, "post")
            return {}
        # ---- the step's ONE device->host fetch --------------------------
        with _span("engine.fetch"):
            host = jax.device_get(fetch)
        self._record_moe(host)

        out: Dict[int, int] = {}
        if live:
            self._ctx_host[:] = host["ctx"]
            if want_stats:
                with _span("engine.promotions"):
                    self._feed_translation_stats(live, host, ctx_pre)
        with _span("engine.commit"):
            if live:
                if K:
                    self._commit_spec(live, host, ctx_pre, out)
                else:
                    for st in live:
                        sid = st.request.seq_id
                        nxt = int(host["next"][self._slot_of[sid]])
                        st.generated.append(nxt)
                        st.new_tokens.append(nxt)
                        st.last_step = self._step_count
                        self._tokens_emitted += 1
                        out[sid] = nxt
                        self._maybe_finish(st, nxt)
            for r, _ in pending:
                nxt = int(host[f"p{r.seq_id}"])
                self._complete_prefill(r, nxt)
                out[r.seq_id] = nxt
        if self._injector is not None:
            # safe point #2: after the commit — under speculation this is
            # the adversarial moment between a window's verify/commit and
            # the next dispatch
            self._run_forced_preempts(
                self._injector.forced_preempts(self._step_count, "post"))
            # crash point "post": this step's commit is fully applied —
            # a crash here loses NOTHING the snapshot cadence covers, it
            # only forces the supervisor to replay from the last snapshot
            crash = getattr(self._injector, "maybe_crash", None)
            if crash is not None:
                crash(self._step_count, "post")
        return out

    def _record_moe(self, host) -> None:
        """Add the fetched MoE counters to the totals and the step log."""
        t = time.perf_counter()
        for kind, runs in (("prefill", host.get("moe_prefill", ())),
                           ("decode", [host["moe_decode"]]
                            if "moe_decode" in host else ())):
            tot = self._moe_totals[kind]
            for pairs, hit in runs:
                tot["executions"] += 1
                tot["routed_pairs"] += int(pairs)
                tot["experts_hit"] += int(hit)
                self._moe_log.append((t, kind, int(pairs), int(hit)))

    def _feed_translation_stats(self, live, host, ctx_pre) -> None:
        """Feed the step's translation telemetry back (PTW-cost
        tracking): globally to the manager and its promoter, and
        attributed per request; then apply the promotions' copies."""
        m = self.manager
        bs = self.cfg.kv_block_size
        K = self.spec_K
        nblk = self.spec.max_blocks_per_seq
        live_slots = [self._slot_of[st.request.seq_id] for st in live]
        live_mask = np.zeros(self.max_batch, bool)
        live_mask[live_slots] = True
        # pre-step block counts: blocks covering positions [0, pos] —
        # [0, pos+K] under speculation, the window the verify dispatch
        # attends — NOT the post-step ctx, whose boundary block may not
        # exist yet — further masked by the device ``mapped`` flag so a
        # failed (swapped) allocation is not recorded as a flexible walk
        # and fed to the promoter
        n_pre = np.minimum((ctx_pre + K) // bs + 1, nblk)
        valid = (live_mask[:, None]
                 & (np.arange(nblk)[None, :] < n_pre[:, None])
                 & np.asarray(host["mapped"][0], bool))
        vpns = (np.arange(self.max_batch)[:, None] * nblk
                + np.arange(nblk)[None, :])
        in_rest = np.asarray(host["in_rest"][0], bool)
        m.record_device_stats(vpns[valid], in_rest[valid],
                              host["accesses"][0][valid])
        # the same telemetry, attributed per request: RestSeg hits vs
        # flexible walks for each sequence's own blocks
        hits_slot = (valid & in_rest).sum(axis=1)
        walks_slot = (valid & ~in_rest).sum(axis=1)
        for st, slot in zip(live, live_slots):
            st.rsw_hits += int(hits_slot[slot])
            st.flex_walks += int(walks_slot[slot])
        m.run_promotions()
        self._apply_copies()

    def _commit_spec(self, live, host, ctx_pre, out) -> None:
        """Variable-length commit of the speculative window.

        The device already advanced ``ctx_len`` by ``n_emit`` in-graph;
        the host walks the emitted tokens in order, stopping early at
        eos / ``max_new_tokens`` exactly where sequential decode would.
        A truncated row's ``ctx_len`` is rewound (one batched scatter —
        upload, not fetch: the single-``device_get`` contract holds), and
        blocks a rejected or truncated tail had crossed into are
        deallocated (they hold nothing committed; KV inside kept blocks
        needs no rewind — positions at or beyond ``ctx_len`` are masked
        by every later read and rewritten before they are attended).
        """
        m = self.manager
        bs = self.cfg.kv_block_size
        K = self.spec_K
        nblk = self.spec.max_blocks_per_seq
        rewinds: Dict[int, int] = {}
        for st in live:
            sid = st.request.seq_id
            slot = self._slot_of[sid]
            pos = int(ctx_pre[slot])
            # capacity clamp: a window tail past the last KV block had
            # its K/V writes range-masked in-graph, so tokens emitted
            # from those query positions are NOT exact — never commit
            # them (the truncation rewind below restores ctx).  Callers
            # need no special max_seq_len sizing; overrun costs
            # re-verification, not correctness.  At cap == 0 even the
            # fed token's K/V write was masked, so NOTHING within the
            # window can ever become exact: the row is out of KV
            # capacity and finishes with a "length" stop.
            cap = self.spec.max_blocks_per_seq * bs - pos
            n_emit = int(host["n_emit"][slot])
            n = min(n_emit, cap) if cap > 0 else 0
            toks = host["next"][slot]
            committed = 0
            for i in range(n):
                t = int(toks[i])
                st.generated.append(t)
                st.new_tokens.append(t)
                out[sid] = t
                committed += 1
                self._maybe_finish(st, t)
                if st.done:
                    break
            # acceptance telemetry counts REALIZED drafts: the ones that
            # entered the stream (committed - 1; the +1 bonus token is
            # the target's own).  Rows sum exactly to the globals by
            # construction (cross-checked in tests).
            st.drafted += K
            st.accepted += max(committed - 1, 0)
            st.last_step = self._step_count
            self._tokens_emitted += committed
            self._spec_drafted += K
            self._spec_accepted += max(committed - 1, 0)
            if cap <= 0 and not st.done:
                self._finish(st, "length")
            if sid not in self._slot_of:
                continue    # finished AND auto-released: state already reset
            new_ctx = pos + committed
            # rewind whenever the host committed fewer tokens than the
            # device advanced IN-GRAPH (n_emit) — eos/max_new truncation
            # AND the capacity clamp above both leave ctx ahead otherwise
            if committed < n_emit:
                rewinds[slot] = new_ctx
                self._ctx_host[slot] = new_ctx
            if self._n_attn_layers:
                # free blocks a rejected/truncated tail faulted in past
                # the committed context.  A LIVE row keeps the block
                # containing its next write position (the engine feeds
                # the committed bonus token there on the very next step:
                # freeing it would be pure free->refault->resync churn,
                # ~25% step overhead measured at K=1).  A row that
                # finished mid-window gets the strict rule — nothing it
                # won't use may stay mapped.
                threshold = new_ctx if st.done else new_ctx + 1
                first_free = (threshold + bs - 1) // bs
                for b in range(first_free,
                               min((pos + K) // bs, nblk - 1) + 1):
                    m.free_block(sid, b)
        if rewinds:
            slots = _pad_pow2(np.fromiter(rewinds.keys(), np.int32,
                                          len(rewinds)),
                              next(iter(rewinds.keys())))
            vals = _pad_pow2(np.fromiter(rewinds.values(), np.int64,
                                         len(rewinds)),
                             next(iter(rewinds.values())))
            self.dstate["ctx_len"] = self.dstate["ctx_len"].at[
                jnp.asarray(slots)].set(
                    jnp.asarray(vals, self.dstate["ctx_len"].dtype))

    # ---------------------------------------------------- streaming output
    @property
    def step_count(self) -> int:
        """Engine steps executed so far (the scheduler's aging clock)."""
        return self._step_count

    def poll(self) -> List[RequestOutput]:
        """Advance the engine one step (if any work remains) and return a
        ``RequestOutput`` per request that produced tokens or finished
        since the previous poll.

        Under overload (``overload_policy="preempt"``, the default) a
        full pool preempts victims to the host KV tier and keeps
        serving; ``PoolExhausted`` survives only for requests that can
        NEVER run — a prompt whose footprint alone exceeds the pool, or
        a queue stuck behind finished-but-unreleased sequences — and
        carries structured occupancy diagnostics (``exc.diag``).

        Raises ``PoolExhausted`` when a step makes NO progress — no
        token decoded, no prompt chunk admitted, no sequence preempted
        or resumed — while requests are still queued: every slot is held
        by a finished-but-unreleased sequence (``auto_release=False``),
        so iterating would spin forever.  Release sequences or enable
        ``auto_release``."""
        self._enforce_deadlines()
        if self.has_unfinished():
            # slot count included: a zero-token finish (capacity stop)
            # that auto-releases its slot IS progress — the freed slot
            # admits a queued request on the next step.  So are
            # preempt/resume events (_progress_events): a step that only
            # rearranged residency is working, not stuck.
            before = (dict(self._prefilling), len(self.waiting),
                      len(self._slot_of), self._progress_events)
            out = self.step()
            if (not out and self.waiting
                    and before == (self._prefilling, len(self.waiting),
                                   len(self._slot_of),
                                   self._progress_events)):
                raise PoolExhausted(
                    f"{len(self.waiting)} queued request(s) cannot be "
                    "admitted and nothing is decoding: release finished "
                    "sequences or construct the engine with "
                    "auto_release=True", **self._pool_diag())
        return self._drain_outputs()

    def stream(self):
        """Iterate ``RequestOutput`` snapshots until every submitted
        request finishes."""
        while self.has_unfinished():
            yield from self.poll()
        # outputs produced by direct step() calls before streaming began
        yield from self._drain_outputs()

    def _drain_outputs(self) -> List[RequestOutput]:
        outs = []
        for sid, st in self._states.items():
            if st.new_tokens or (st.done and not st.finish_reported):
                outs.append(RequestOutput(
                    seq_id=sid, new_token_ids=tuple(st.new_tokens),
                    token_ids=tuple(st.generated), finished=st.done,
                    finish_reason=st.finish_reason))
                st.new_tokens = []
                if st.done:
                    st.finish_reported = True
        return outs

    # -------------------------------------------- cancellation / deadlines
    def cancel(self, seq_id: int, reason: str = "cancelled") -> bool:
        """Terminate a request wherever it is in its lifecycle — queued,
        mid-chunk prefill, decoding, or parked on the host KV tier — and
        reclaim everything it holds: its sequence slot, KV blocks, prefix
        cache refcounts and ledger claims (``check_invariants`` stays
        green afterwards, pinned in tests/test_recovery.py).

        The final ``RequestOutput`` carries ``finished=True`` with
        ``finish_reason="cancelled"`` (or ``"deadline"`` when invoked by
        the deadline sweep) and whatever tokens were generated before the
        cut.  Returns False — touching nothing — when the id is unknown
        or the request already finished.  The slot is force-released even
        under ``auto_release=False``: a cancelled request's holder has by
        definition stopped consuming it."""
        st = self._states.get(seq_id)
        if st is None or st.done:
            return False
        req = st.request
        if self._current is not None and self._current.seq_id == seq_id:
            self._current = None
        try:
            # queued (never admitted) or preempted requests sit in the
            # scheduler queue; live decoders do not
            self.scheduler.remove(req)
        except (ValueError, AttributeError):
            pass
        # a host-tier copy dies with the cancel: nothing left to resume
        # (preempt_request already freed the manager/ledger side)
        self._preempted.pop(seq_id, None)
        self._pending_samp = [(s, r) for s, r in self._pending_samp
                              if r.seq_id != seq_id]
        st.done = True
        st.finish_reason = reason
        if reason == "deadline":
            self._deadline_expired += 1
        else:
            self._cancelled += 1
        # a cancel IS progress for poll()'s no-progress detector: the
        # freed capacity admits a queued request on the next step
        self._progress_events += 1
        if seq_id in self._slot_of:
            self.release(seq_id)     # frees slot, blocks, pins, ledger
        else:
            # queued / preempted: no slot to tear down (preempt already
            # freed the manager side), only the registry bookkeeping
            rq = self.requests.pop(seq_id, None)
            self.finished[seq_id] = rq if rq is not None else req
            self._prefilling.pop(seq_id, None)
            self._chain_cache.pop(seq_id, None)
        if self.metrics is not None:
            self.metrics.on_finish(seq_id, self._step_count,
                                   len(st.generated), reason)
        return True

    def _enforce_deadlines(self) -> None:
        """Cancel every live request whose wall-clock budget elapsed
        (``Request.deadline_ms``), with ``finish_reason="deadline"``.
        Called at the top of ``poll()`` — deadline enforcement rides the
        serving loop, costing one clock read per poll and nothing when
        no request carries a deadline."""
        now = None
        for sid in [s for s, st in self._states.items()
                    if not st.done and st.deadline_at is not None]:
            if now is None:
                now = time.perf_counter()
            st = self._states[sid]
            if st.deadline_at is not None and now >= st.deadline_at:
                self.cancel(sid, reason="deadline")

    # ------------------------------------------------------------ teardown
    def release(self, seq_id: int) -> None:
        self.manager.free_sequence(seq_id)
        self._chain_cache.pop(seq_id, None)
        slot = self._slot_of.pop(seq_id)
        self.dstate["ctx_len"] = self.dstate["ctx_len"].at[slot].set(0)
        self._ctx_host[slot] = 0
        if self.spec_K:
            # a recycled slot must not draft from its predecessor's tokens
            self.dstate["hist"] = self.dstate["hist"].at[slot].set(-1)
        req = self.requests.pop(seq_id, None)
        if req is not None:
            self.finished[seq_id] = req
        if self._current is not None and self._current.seq_id == seq_id:
            self._current = None
        self._prefilling.pop(seq_id, None)
        self._sync_translation()

    # --------------------------------------------------- snapshot / restore
    _SNAP_FIELDS = (
        "requests", "finished", "_states", "_current", "_slot_of",
        "_prefilling", "_pending_samp", "_step_count", "admission_log",
        "_preempted", "_swap_bytes_out", "_swap_bytes_in",
        "_progress_events", "_request_preempts", "_request_resumes",
        "_dropped_preempts", "_dropped_resumes", "_tokens_emitted",
        "_spec_drafted", "_spec_accepted", "_cancelled",
        "_deadline_expired", "_chain_cache",
    )

    def snapshot(self) -> EngineSnapshot:
        """Capture the COMPLETE serving state as one portable value.

        Device side: every decode-state array except the tar/sf/flex
        translation mirrors — those are pure functions of the host tables
        and are rebuilt on restore through the exact
        ``_sync_translation(full=True)`` path live serving uses, so the
        snapshot never stores the same truth twice.  One batched
        ``device_get`` fetches everything (KV pools, ctx_len, recurrent
        ssm/conv/cross rows, the spec ``hist`` matrix, per-slot sampling
        params + PRNG keys).

        Host side: ONE ``pickle.dumps`` of the manager (TAR/SF/flex
        tables, AllocLedger, refcounts), prefix cache (directory + pins
        — it references the SAME manager object, and pickle's memo
        preserves that sharing), scheduler queue, request registries and
        ``RequestState``s (mid-chunk prefill progress, preempted
        host-tier sequences included), pending sampling scatters and all
        monotone counters.  Absolute ``deadline_at`` clocks are
        rebased to REMAINING budget (a monotonic timestamp is
        meaningless in the restoring process).

        Legal call points are step boundaries only — the same safe
        points as ``preempt_request`` — which is where
        ``ResilientServe`` calls it.  The snapshot is a value: it stays
        valid after the engine advances, and restoring it on a fresh
        engine of the same config replays bit-identically."""
        # pending slot migrations must land first so the fetched pool
        # bytes agree with the manager's (pickled) post-copy slot map
        self._apply_copies()
        # ``np.array`` copies: on the CPU a fetched array can be a view of
        # the device buffer, which the next step's donation must not meet
        dstate = {k: np.array(v) for k, v in jax.device_get(
            {k: v for k, v in self.dstate.items()
             if k not in ("tar", "sf", "flex")}).items()}
        now = time.perf_counter()
        deadline_remaining = {
            sid: st.deadline_at - now
            for sid, st in self._states.items()
            if st.deadline_at is not None and not st.done}
        payload: Dict[str, Any] = {
            f: getattr(self, f) for f in self._SNAP_FIELDS}
        payload["manager"] = self.manager
        payload["prefix_cache"] = self.prefix_cache
        payload["scheduler"] = self.scheduler
        payload["_ctx_host"] = self._ctx_host
        payload["_shard_swap_out"] = self._shard_swap_out
        payload["_shard_swap_in"] = self._shard_swap_in
        payload["deadline_remaining"] = deadline_remaining
        payload["clock"] = now
        # the scheduler's back-pointer would drag the whole Engine (and
        # its params) into the blob; strip it around the dump
        sched = self.scheduler
        bound = getattr(sched, "_bound_engine", None)
        if bound is not None:
            sched._bound_engine = None
        try:
            blob = pickle.dumps(payload, pickle.HIGHEST_PROTOCOL)
        finally:
            if bound is not None:
                sched._bound_engine = bound
        return EngineSnapshot(version=SNAPSHOT_VERSION,
                              step=self._step_count, dstate=dstate,
                              host_blob=blob)

    def restore(self, snap: EngineSnapshot) -> None:
        """Overwrite this engine's serving state with ``snap``'s.

        The engine must have the same configuration the snapshot was
        taken under (same arch/pool/mesh shapes — the device key set is
        checked loudly).  Everything live is discarded: requests
        submitted after the snapshot are gone and must be resubmitted by
        the caller (``ResilientServe`` journals and replays them).
        After restore the engine continues bit-identically to the run
        that took the snapshot — pinned by the crash oracle in
        tests/test_recovery.py across greedy/sampled × spec on/off ×
        prefix-cache on/off × (1,2) mesh."""
        if snap.version != SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot version {snap.version} != engine "
                f"{SNAPSHOT_VERSION}: cross-version restore unsupported")
        expect = {k for k in self.dstate if k not in ("tar", "sf", "flex")}
        got = set(snap.dstate)
        if got != expect:
            raise ValueError(
                "snapshot device state does not match this engine "
                f"config: missing {sorted(expect - got)}, unexpected "
                f"{sorted(got - expect)}")
        host = pickle.loads(snap.host_blob)
        for f in self._SNAP_FIELDS:
            setattr(self, f, host[f])
        self.manager = host["manager"]
        self.prefix_cache = host["prefix_cache"]
        if getattr(self.scheduler, "_bound_engine", None) is self:
            self.scheduler._bound_engine = None
        self.scheduler = host["scheduler"]
        try:
            self.scheduler._bound_engine = self
        except AttributeError:
            pass
        self._ctx_host = np.asarray(host["_ctx_host"], np.int64).copy()
        self._shard_swap_out = np.asarray(host["_shard_swap_out"],
                                          np.int64).copy()
        self._shard_swap_in = np.asarray(host["_shard_swap_in"],
                                         np.int64).copy()
        # deadline budgets restart from the remaining time at snapshot:
        # the crash + restore pause does not count against a request
        now = time.perf_counter()
        for sid, rem in host["deadline_remaining"].items():
            st = self._states.get(sid)
            if st is not None:
                st.deadline_at = now + rem
        # the queue-wait clocks move by the same pause
        shift = now - host.get("clock", now)
        for st in self._states.values():
            st.submitted_at += shift
            if st.admitted_at is not None:
                st.admitted_at += shift
        # device state: put the fetched arrays back (with the mesh's
        # shardings when sharded — specs computed from the CURRENT
        # dstate before overwriting, the key sets are identical) as
        # buffers the engine owns: a zero-copy view of the snapshot's
        # numpy array could not be donated to the next step
        specs = (kv_state_specs(self.dstate, self.spec)
                 if self.mesh is not None else None)
        for k, v in snap.dstate.items():
            self.dstate[k] = jnp.array(
                v, copy=True, device=(None if specs is None else
                                      NamedSharding(self.mesh, specs[k])))
        # translation mirrors: rebuilt from the restored host tables via
        # the one true sync path (also clears the manager's dirty set)
        self._synced_full = False
        self._sync_translation(full=True)
        if self.metrics is not None:
            # the logger differentiates ABSOLUTE counters: rebase its
            # baseline so the rewind does not produce negative deltas
            self.metrics.rebase(self._metrics_counters())

    def _kv_block_bytes(self) -> int:
        """Device bytes one pool block occupies across both KV pools
        (all attention layers): the unit behind ``bytes_saved``."""
        k = self.dstate.get("k_pool")
        if k is None:
            return 0
        n_slots = int(k.shape[1])
        return int((k.nbytes + self.dstate["v_pool"].nbytes)
                   // max(n_slots, 1))

    # -------------------------------------------------------- live metrics
    def _metrics_counters(self) -> Dict[str, Any]:
        """ABSOLUTE monotone counters for the metrics logger (it
        differentiates them into per-step deltas).  Host-side reads
        only — the logger's totals agree with ``stats()`` at every step
        by construction (pinned in tests/test_metrics.py)."""
        m = self.manager
        pc = self.prefix_cache
        c: Dict[str, Any] = {
            "tokens": self._tokens_emitted,
            "rsw_hits": int(m.stats.get("rsw_hits", 0)),
            "flex_walks": int(m.stats.get("flex_walks", 0)),
            "swap_faults": int(m.stats.get("faults", 0)),
            "spec_drafted": self._spec_drafted,
            "spec_accepted": self._spec_accepted,
            "request_preempts": self._request_preempts,
            "request_resumes": self._request_resumes,
            "swap_bytes_out": self._swap_bytes_out,
            "swap_bytes_in": self._swap_bytes_in,
            "prefix_lookups": int(pc.stats["lookups"]) if pc else 0,
            "prefix_hits": int(pc.stats["hits"]) if pc else 0,
            "cancelled": self._cancelled,
            "deadline_expired": self._deadline_expired,
        }
        if self.cfg.moe_num_experts:
            # [decode, prefill], as the list-valued counters are emitted
            c["moe_routed_pairs"] = [self._moe_totals[k]["routed_pairs"]
                                     for k in ("decode", "prefill")]
            c["moe_experts_hit"] = [self._moe_totals[k]["experts_hit"]
                                    for k in ("decode", "prefill")]
        if self.partition is not None:
            c["shard_swap_bytes_out"] = [int(x)
                                         for x in self._shard_swap_out]
            c["shard_swap_bytes_in"] = [int(x)
                                        for x in self._shard_swap_in]
        return c

    def _metrics_gauges(self) -> Dict[str, Any]:
        """Point-in-time gauges copied into the step event verbatim."""
        m = self.manager
        total = self.hybrid_cfg.total_slots
        mapped = sum(1 for i in m.blocks.values() if i.slot >= 0)
        return {
            "pool_blocks": total,
            "mapped_blocks": mapped,
            "occupancy": mapped / max(total, 1),
            "live": sum(1 for sid in self.requests
                        if not self._states[sid].done),
            "queued": len(self.waiting),
            "host_tier_seqs": len(self._preempted),
        }

    def stats(self) -> dict:
        """Global manager counters plus ``"per_request"``: RestSeg hits /
        flexible walks / swap faults — and, under speculative decoding,
        drafts proposed (``drafted``) and accepted into the stream
        (``accepted``) — attributed to each seq_id (decode steps; live
        and finished requests both included).  The per-request
        ``drafted``/``accepted`` rows sum exactly to the global
        ``spec_drafted``/``spec_accepted`` counters (same attribution
        invariant as rsw_hits/flex_walks).  ``queue_wait_s`` is submit ->
        first prompt chunk admitted and ``admitted_at`` that admission's
        ``time.perf_counter`` time (both None while still queued)."""
        s = dict(self.manager.stats)
        s["spec_drafted"] = self._spec_drafted
        s["spec_accepted"] = self._spec_accepted
        # overload/host-tier telemetry (ISSUE 6): sequence-granularity
        # preempt/resume counts, current host-tier residency, and the
        # host<->device swap traffic in bytes
        # request_preempts/resumes are MONOTONE engine counters, not
        # sums over the per-request rows: a finished request's row is
        # dropped on seq_id reuse, so a row sum would silently shrink.
        # The dropped share is surfaced too — sum(per-request rows) +
        # dropped == global (asserted in check_invariants, pinned with
        # a reuse test).
        s["overload"] = {
            "preempted_seqs": int(self.manager.stats.get("preempt_out", 0)),
            "resumed_seqs": int(self.manager.stats.get("preempt_in", 0)),
            "host_tier_seqs": len(self._preempted),
            "swap_bytes_out": self._swap_bytes_out,
            "swap_bytes_in": self._swap_bytes_in,
            "request_preempts": self._request_preempts,
            "request_resumes": self._request_resumes,
            "dropped_request_preempts": self._dropped_preempts,
            "dropped_request_resumes": self._dropped_resumes,
        }
        # prefix-cache telemetry: the per-request cached_blocks rows sum
        # exactly to the global dedup_blocks counter (same attribution
        # invariant as rsw_hits/flex_walks — cross-checked in tests)
        pc = self.prefix_cache
        # request-lifecycle robustness (ISSUE 10): explicit cancels and
        # wall-clock deadline expiries (monotone; survive snapshot/restore)
        s["lifecycle"] = {
            "cancelled": self._cancelled,
            "deadline_expired": self._deadline_expired,
        }
        s["prefix_cache"] = {
            "enabled": pc is not None,
            "lookups": int(pc.stats["lookups"]) if pc else 0,
            "hits": int(pc.stats["hits"]) if pc else 0,
            "dedup_blocks": int(pc.stats["dedup_blocks"]) if pc else 0,
            "bytes_saved": (int(pc.stats["dedup_blocks"])
                            * self._kv_block_bytes() if pc else 0),
            "inserts": int(pc.stats["inserts"]) if pc else 0,
            "insert_bypass": int(pc.stats["insert_bypass"]) if pc else 0,
            "evictions": int(pc.stats["evictions"]) if pc else 0,
            "cached_blocks": pc.n_entries if pc else 0,
        }
        # KV pool writes outside the step programs (slot migrations and
        # resume scatters): in-place dispatches and the slots written
        s["pool_writes"] = dict(self._pool_writes)
        if self.cfg.moe_num_experts:
            # per program kind: executions, routed pairs and experts hit
            # summed over MoE layers; ``steps`` holds the last
            # ``MOE_LOG_STEPS`` executions as (fetch time on
            # ``time.perf_counter``, kind, routed pairs, experts hit)
            s["moe"] = {k: dict(v) for k, v in self._moe_totals.items()}
            s["moe"]["steps"] = list(self._moe_log)
        s["per_request"] = {
            sid: {"rsw_hits": st.rsw_hits, "flex_walks": st.flex_walks,
                  "swap_faults": st.swap_faults, "drafted": st.drafted,
                  "accepted": st.accepted,
                  "cached_blocks": st.cached_blocks,
                  "preempts": st.preempts, "resumes": st.resumes,
                  "queue_wait_s": (None if st.admitted_at is None else
                                   st.admitted_at - st.submitted_at),
                  "admitted_at": st.admitted_at}
            for sid, st in self._states.items()}
        if self.partition is not None:
            # per-shard view: each key sums EXACTLY to its global above
            # (shared mutation sites, not post-hoc reconciliation).
            # Spec counters describe replicated compute, charged to
            # shard 0 — NOT scaled by the shard count.
            s["shards"] = [
                {"rsw_hits": int(ss.get("rsw_hits", 0)),
                 "flex_walks": int(ss.get("flex_walks", 0)),
                 "swap_bytes_out": int(self._shard_swap_out[i]),
                 "swap_bytes_in": int(self._shard_swap_in[i]),
                 "spec_drafted": self._spec_drafted if i == 0 else 0,
                 "spec_accepted": self._spec_accepted if i == 0 else 0}
                for i, ss in enumerate(self.manager.shard_stats)]
        return s

    def check_invariants(self) -> None:
        """Engine-level oracle on top of the manager's: the device
        translation mirrors must equal the host tables (with zeroed /
        -1 padding past the real sizes under the mesh layout), and the
        per-shard swap-byte attribution must sum exactly to the global
        swap counters."""
        self.manager.check_invariants()
        if self.prefix_cache is not None:
            self.prefix_cache.check_invariants()
        # preempt/resume accounting: the monotone globals must equal the
        # surviving per-request rows plus the counts banked when rows
        # were dropped on seq_id reuse (ISSUE 9 bugfix — the old row-sum
        # global silently shrank on reuse)
        assert (sum(st.preempts for st in self._states.values())
                + self._dropped_preempts == self._request_preempts), \
            "per-request preempts + dropped != global request_preempts"
        assert (sum(st.resumes for st in self._states.values())
                + self._dropped_resumes == self._request_resumes), \
            "per-request resumes + dropped != global request_resumes"
        m = self.manager
        tar = np.asarray(jax.device_get(self.dstate["tar"]))[0]
        sf = np.asarray(jax.device_get(self.dstate["sf"]))[0]
        flex = np.asarray(jax.device_get(self.dstate["flex"]))[0]
        n_sets, flat = m.tar.shape[0], m.flex_table.reshape(-1)
        assert (tar[:n_sets] == m.tar).all(), "device TAR != host TAR"
        assert (sf[:n_sets] == m.sf).all(), "device SF != host SF"
        assert (flex[:flat.size] == flat).all(), "device flex != host flex"
        if self.partition is not None:
            assert (tar[n_sets:] == 0).all(), "padded TAR rows dirtied"
            assert (sf[n_sets:] == 0).all(), "padded SF rows dirtied"
            assert (flex[flat.size:] == -1).all(), "padded flex dirtied"
            assert int(self._shard_swap_out.sum()) == self._swap_bytes_out, \
                "per-shard swap-out bytes != global"
            assert int(self._shard_swap_in.sum()) == self._swap_bytes_in, \
                "per-shard swap-in bytes != global"
