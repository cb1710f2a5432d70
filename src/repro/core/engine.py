"""KVSwap engine: prefill → disk, overlap-pipelined sparse decode (§3.4).

Orchestration is host-side Python (as in the paper's runtime); all compute is
jitted JAX.  The disk tier is the real memmap store; I/O *time* is modeled by
the :class:`DiskSpec` accountant, and per-step latency is assembled with the
paper's layer-pipelined overlap (I/O for layer *i* overlaps compute of layer
*i−1*).

Two execution modes, selected by :attr:`EngineConfig.async_io`:

* **sync** (default) — every group read happens inline on the critical path,
  exactly where the prediction for that layer lands;
* **async** — the structural pipeline of §3.3/§3.4: as soon as layer *i*'s
  input is available, the prediction for layer *i+1* is scored and its group
  reads are handed to a background :class:`~repro.io.PrefetchWorker`; a
  :class:`~repro.io.DoubleBuffer` holds layer *i+1*'s groups while layer *i*
  computes.  The two modes run the same per-layer numeric code on the same
  inputs, so decoded tokens are **bit-identical** — only wall-clock changes.

Orthogonally, :attr:`EngineConfig.warm_budget_bytes` inserts a budgeted
host-RAM **warm tier** (:mod:`repro.tiers`) between the per-layer reuse
buffers and the disk store: reuse-evicted groups are kept as per-group
int8 under one global LRU byte budget and served back at memcpy+dequantize
cost instead of a disk re-read.  0 (default) disables it; at ``kv_bits=8``
enabling it is token-bit-identical to the disabled control.

Orthogonally, :attr:`EngineConfig.device_resident` picks where the selected
KV working set lives between steps:

* **device-resident** (default) — each layer's reuse buffer has a device
  mirror updated by scatter-writing only newly fetched groups; the decode
  context is gathered on device by slot permutation
  (:meth:`~repro.models.transformer.TransformerAdapter.gather_context`),
  fresh ``k_new/v_new`` accumulate in a device rolling buffer flushed at
  the end of a step in which groups complete (one program and one download
  for every completed layer and row), and prediction is one fused
  dispatch.  Only misses cross the host↔device boundary, so per-step
  upload bytes shrink by the reuse hit rate (75–81 % of groups, Fig. 8).
* **host-gather** (``device_resident=False``, the seed behavior) — every
  layer re-materializes the full context on host and re-uploads it.  Kept as
  the A/B control; decoded tokens are **bit-identical** between the two.

**Per-slot request lifecycle (continuous batching).**  Every piece of
per-sequence state — sequence length, compressed-cache watermark, rolling
fill, reuse slots, disk extents — is tracked **per batch row**, and an
active-row mask is threaded through prediction, fetch and the modeled
compute/IO accounting.  :meth:`KVSwapEngine.admit_row` prefills one prompt
into a free slot (restoring a cached prefix when a
:class:`~repro.cache.PrefixCache` is handed in) while the other slots keep
decoding, and :meth:`KVSwapEngine.retire_row` frees the slot's mapping-table
groups, reuse-buffer slots, device-mirror addressing, and disk extents for
the next tenant.  Inactive (retired or never-admitted) rows select no
groups, so they issue **no disk reads and charge no modeled time**.  The
classic lockstep entry points (:meth:`prefill` + :meth:`decode_step` over a
whole batch) are the same code path with every row admitted at once, which
is what keeps continuous batching bit-identical to the static batcher for
identical arrival patterns.  :class:`repro.serving.api.ServeSession` is the
front end that drives this lifecycle.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.blocks import chain_blocks
from repro.core import hardware
from repro.core.adapter import ModelAdapter
from repro.core.lowrank import LowRankAdapter, compress_k, fit_adapter
from repro.core.manager import KVCacheManager
from repro.core.offload import DISKS, DiskSpec, IOAccountant, KVDiskStore
from repro.core.predictor import PredictorConfig
from repro.core.reuse_buffer import ReuseBuffer
from repro.core.rolling_buffer import RollingBuffer
from repro.faults.errors import CorruptBlockError, StorageFault
from repro.faults.retry import RetryPolicy
from repro.io import DoubleBuffer, PrefetchWorker, ReadRun, ReadScheduler
from repro.obs import NULL_OBS, PrefetchQualityMeter
from repro.utils import stats as stats_util

# PrefetchWorker pool size (async mode only)
IO_THREADS = 2
# bounded retry-with-backoff for disk reads (docs/robustness.md): total
# attempts per coalesced run, exponential modeled backoff from IO_BACKOFF_S
# between them (charged as accountant stall time, never slept)
IO_MAX_ATTEMPTS = 3
IO_BACKOFF_S = 0.002


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Runtime parameters — the tuple the offline tuner (§3.5) produces.

    Knob-by-knob (see ``docs/tuning.md`` for how the tuner picks them):

    * ``group_size`` (**G**) — tokens per KV group, the unit of disk layout,
      prediction, and transfer.  Larger G → bigger sequential reads (better
      effective bandwidth, Fig. 2) but coarser selection.
    * ``n_select`` (**M**) — groups preloaded per layer per decode step; the
      attention budget is ``M·G`` tokens.
    * ``rank`` (**r**) — low-rank adapter width for the compressed K cache;
      compression ratio σ = ``H_k·d / r``.  Higher r → better prediction
      recall, more resident metadata memory.
    * ``reuse_capacity`` (**C**) — reuse-buffer slots (groups) per layer per
      sequence; adjacent steps share 75–81 % of critical groups (Fig. 8), so
      C converts memory into skipped disk reads.
    * ``max_seq`` — KV capacity in tokens (bounds the memmap file).
    * ``disk`` — which :class:`DiskSpec` prices modeled I/O
      ("nvme"/"ufs"/"emmc").
    * ``warm_budget_bytes`` — host-RAM byte budget for the quantized warm
      tier (:mod:`repro.tiers`) between the reuse buffer and disk: groups
      evicted from the reuse buffer are kept as per-group-scaled int8 under
      a global LRU budget and served back at memcpy+dequantize cost instead
      of a disk re-read.  0 (default) disables the tier entirely — tokens
      and ``StepStats`` are then byte-identical to an engine without it.
    * ``predict_from`` — "prev" scores layer *i* from layer *i−1*'s input
      (cross-layer similarity, §3.3), which is what makes prefetch
      overlappable; "self" predicts from the layer's own input (exact timing
      of InfiniGen-style online prediction, no overlap possible).
    * ``kv_bits`` — 16 stores the raw dtype on disk; 8 stores per-group
      scaled int8 (§7 "low-bit KV"), shrinking every group read.
    * ``use_pallas`` — route gather-attention and the fused predictor
      through the Pallas kernels.
    * ``device_resident`` — keep the selected-KV working set on device
      between steps (reuse-mirror delta uploads + device rolling buffer +
      fused prediction); ``False`` is the host-gather control path with
      bit-identical tokens.  Note C (``reuse_capacity``) then also bounds
      device memory: ``2·C·G·H_kv·d·itemsize`` bytes per KV layer.
    * ``async_io`` — run group preloading on the background worker
      (:mod:`repro.io`); bit-identical tokens, overlapped wall-clock.
    * ``coalesce_gap`` — largest unrequested-group gap the
      :class:`ReadScheduler` reads through to keep a request sequential;
      0 merges only strictly adjacent groups.
    """

    group_size: int = 4            # G
    n_select: int = 100            # M (selected groups per layer per step)
    rank: int = 64                 # r  (σ = H_k·d / r)
    reuse_capacity: int = 160      # C (groups per layer per sequence)
    max_seq: int = 4096            # KV capacity (tokens)
    disk: str = "nvme"
    warm_budget_bytes: int = 0     # host-RAM warm tier budget (0 = disabled)
    predict_from: str = "prev"     # "prev" (paper, overlappable) | "self"
    kv_bits: int = 16              # 16 = raw dtype on disk; 8 = int8 (§7)
    use_pallas: bool = False       # route attention through the Pallas kernel
    device_resident: bool = True   # device-side working set, delta uploads
    dtype: str = "float32"
    compute: str = "jetson-orin-agx"  # timing model for simulated throughput
    async_io: bool = False         # background prefetch pipeline (repro.io)
    coalesce_gap: int = 0          # ReadScheduler gap coalescing (groups)

    @property
    def disk_spec(self) -> DiskSpec:
        return DISKS[self.disk]

    @property
    def np_dtype(self):
        return np.dtype(self.dtype)


class PublishResult(int):
    """Return of :meth:`KVSwapEngine.publish`: behaves as the plain block
    count it always was (``+=`` accounting in the serving layer keeps
    working), but also carries ``heads`` — per published row, the deepest
    resident block id of that row's hash chain.  Disagg prefill tickets
    hand this id across the prefill/decode boundary so the decode side can
    restore the chain by reference instead of re-hashing the prompt.
    ``heads[row] is None`` when nothing of the row's chain is resident
    (e.g. the prompt is shorter than one block)."""

    def __new__(cls, published: int, heads: dict[int, str | None] | None = None):
        self = super().__new__(cls, published)
        self.heads = heads or {}
        return self


@dataclasses.dataclass
class StepStats:
    """Per-decode-step accounting.

    ``io/compute/pipelined_seconds`` are *modeled* (DiskSpec + ComputeSpec)
    and identical between sync and async modes; ``wall_seconds`` and
    ``io_wait_seconds`` are *measured* on the host, so async mode shows the
    read time actually hidden under compute (``io_wait < io_seconds``-ish).
    """

    io_seconds: float = 0.0          # modeled fetch-serve time (disk + warm tier)
    compute_seconds: float = 0.0     # modeled compute time, summed over layers
    pipelined_seconds: float = 0.0   # modeled layer-pipelined step latency
    io_bytes: int = 0                # cumulative disk bytes read since engine start
    io_requests: int = 0             # cumulative read requests since start
    warm_bytes: int = 0              # warm-tier-served bytes this step (disk units)
    wall_seconds: float = 0.0        # host time to dispatch this step (logits not awaited)
    io_wait_seconds: float = 0.0     # measured wall time blocked on fetches
    h2d_bytes: int = 0               # host→device KV payload bytes this step
    active_rows: int = 0             # rows decoded this step (continuous batching)
    # prefetch quality (repro.obs.quality): pooled integer counts over this
    # step's (layer, row) selections, scored as 1-step lookahead against the
    # previous step's selections.  Ratios of sums aggregate correctly across
    # steps, so the counts are stored and the ratios derived.
    pred_shared_groups: int = 0      # |prev ∩ cur| summed over (layer, row)
    pred_prev_groups: int = 0        # |prev| summed over (layer, row)
    pred_cur_groups: int = 0         # |cur| summed over (layer, row)
    stale_groups: int = 0            # reuse-resident but unselected this step
    resident_groups: int = 0         # reuse-resident at selection time
    flushed_groups: int = 0          # (KV layer, row) groups written to disk this step

    @property
    def overlap_saved_seconds(self) -> float:
        """Modeled time the pipeline hides: serial − pipelined."""
        return max(0.0, self.io_seconds + self.compute_seconds - self.pipelined_seconds)

    @property
    def pred_precision(self) -> float:
        """Of last step's selection, the fraction re-selected this step —
        what a 1-step lookahead prefetcher's precision would have been."""
        return self.pred_shared_groups / self.pred_prev_groups \
            if self.pred_prev_groups else 0.0

    @property
    def pred_recall(self) -> float:
        """Of this step's selection, the fraction last step's selection
        already covered — a lookahead prefetcher's recall."""
        return self.pred_shared_groups / self.pred_cur_groups \
            if self.pred_cur_groups else 0.0

    @property
    def stale_group_rate(self) -> float:
        """Of the groups resident in the reuse buffers at selection time,
        the fraction this step did not select (reclaimable dead weight)."""
        return self.stale_groups / self.resident_groups \
            if self.resident_groups else 0.0


def summarize_steps(steps: Sequence[StepStats]) -> dict:
    """Mean per-step modeled + measured overlap over a window of steps.

    Shared by :meth:`KVSwapEngine.overlap_report` (whole-engine view) and the
    serving session, which summarizes only its own flush window of a
    persistent engine's ``step_log``.

    ``step_seconds_p50/p95/p99`` are tail percentiles of the modeled
    per-step latency (``pipelined_seconds``) over the window — means hide
    exactly the straggler steps (reuse-buffer cold starts, C<M overflow
    rounds) that break per-token SLOs, so the serving harness and the
    engine report the same tail statistic from the same helper
    (:func:`repro.utils.stats.percentile`).
    """
    if not steps:
        return {}
    n = len(steps)
    mean = lambda f: sum(f(s) for s in steps) / n
    tails = stats_util.percentiles([s.pipelined_seconds for s in steps])
    # prefetch quality pooled over the window: ratios of summed counts, not
    # means of per-step ratios (sparse steps would otherwise be overweighted)
    shared = sum(s.pred_shared_groups for s in steps)
    prev = sum(s.pred_prev_groups for s in steps)
    cur = sum(s.pred_cur_groups for s in steps)
    stale = sum(s.stale_groups for s in steps)
    resident = sum(s.resident_groups for s in steps)
    return {
        "io_seconds": mean(lambda s: s.io_seconds),
        "compute_seconds": mean(lambda s: s.compute_seconds),
        "pipelined_seconds": mean(lambda s: s.pipelined_seconds),
        "overlap_saved_seconds": mean(lambda s: s.overlap_saved_seconds),
        "wall_seconds": mean(lambda s: s.wall_seconds),
        "io_wait_seconds": mean(lambda s: s.io_wait_seconds),
        "h2d_bytes": mean(lambda s: s.h2d_bytes),
        "active_rows": mean(lambda s: s.active_rows),
        "warm_bytes": mean(lambda s: s.warm_bytes),
        "pred_precision": shared / prev if prev else 0.0,
        "pred_recall": shared / cur if cur else 0.0,
        "stale_group_rate": stale / resident if resident else 0.0,
        "flushed_groups": sum(s.flushed_groups for s in steps),
        **{f"step_seconds_{k}": v for k, v in tails.items()},
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def _klr_append(k_lr: jax.Array, rows: jax.Array, start: jax.Array) -> jax.Array:
    """Write ``rows [B, G, r]`` into the preallocated ``k_lr [B, cap, r]``."""
    return jax.lax.dynamic_update_slice(k_lr, rows, (0, start, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _klr_append_row(k_lr: jax.Array, rows: jax.Array, bi: jax.Array,
                    start: jax.Array) -> jax.Array:
    """Write ``rows [1, n, r]`` into row ``bi`` of ``k_lr [B, cap, r]`` at
    token offset ``start`` — the admission unit of continuous batching."""
    return jax.lax.dynamic_update_slice(k_lr, rows, (bi, start, 0))


@functools.partial(jax.jit, static_argnames=("dtype",), donate_argnums=(0,))
def _flush_groups(k_lr: tuple, a: jax.Array, done: jax.Array, start: jax.Array,
                  groups_k, groups_v, *, dtype):
    """Every group a decode step completed, in one program.

    ``groups_k[j]``/``groups_v[j]`` hold KV layer ``j``'s ``[B, G, H_kv, d]``
    groups (the device rolling tails, or the host rolling buffers stacked
    ``[L_kv, B, G, H_kv, d]``); ``done [L_kv, B]`` marks the (layer, row)
    groups that completed; ``start [B]`` is each row's ``k_lr`` watermark.
    Each completed group, cast to the storage ``dtype`` exactly as the disk
    stores it, is compressed and written into ``k_lr[j]`` at its row's
    watermark; other rows keep their contents.  Returns ``(kv, k_lr)``:
    ``kv`` is the cast K and V, each stacked ``[L_kv, B, G, H_kv, d]``, for
    the disk write, or ``None`` when ``groups_v`` is empty (the host path
    has written its groups already)."""
    k = [groups_k[j].astype(dtype) for j in range(len(k_lr))]
    b, g, hk, d = k[0].shape
    adapter = LowRankAdapter(a=a, n_kv_heads=hk, head_dim=d)
    new = []
    for j, kl in enumerate(k_lr):
        rows = compress_k(k[j].astype(jnp.float32), adapter)        # [B, G, r]
        # rows that did not complete point past the end and are dropped
        t = jnp.where(done[j], start, kl.shape[1])[:, None] + jnp.arange(g)
        new.append(kl.at[jnp.arange(b)[:, None], t].set(rows, mode="drop"))
    if not groups_v:
        return None, tuple(new)
    v = [groups_v[j].astype(dtype) for j in range(len(k_lr))]
    return (jnp.stack(k), jnp.stack(v)), tuple(new)


@functools.partial(jax.jit, donate_argnums=(0,))
def _tail_write(tail: jax.Array, new: jax.Array, fidx: jax.Array,
                active: jax.Array) -> jax.Array:
    """Scatter one decoded token per row into the device rolling mirror.

    ``tail [B, G, H_kv, d]``; ``new [B, H_kv, d]``; ``fidx [B]`` each row's
    write position (its pre-append fill); ``active [B]`` bool — inactive rows
    keep their current contents (their fill does not advance either)."""
    rows = jnp.arange(tail.shape[0])
    cur = tail[rows, fidx]
    upd = jnp.where(active[:, None, None], new.astype(tail.dtype), cur)
    return tail.at[rows, fidx].set(upd)


@functools.partial(jax.jit, donate_argnums=(0,))
def _ring_row(ring: jax.Array, bi: jax.Array, row: jax.Array) -> jax.Array:
    """Row ``bi`` of a window ring ``[B, W, H_kv, d]`` replaced by ``row``."""
    return jax.lax.dynamic_update_index_in_dim(ring, row.astype(ring.dtype), bi, 0)


class KVSwapEngine:
    """Serve one batch of sequences with the KVSwap runtime."""

    def __init__(
        self,
        model: ModelAdapter,
        params,
        cfg: EngineConfig,
        *,
        batch: int,
        adapter: LowRankAdapter | None = None,
        calib_k: np.ndarray | None = None,
        obs=None,
        faults=None,
    ):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.batch = batch
        # observability handle (repro.obs.Observability), passed alongside —
        # never inside — the frozen, asdict-serialized EngineConfig.  The
        # shared NULL_OBS default keeps every hot-path guard to one
        # attribute load + bool test.
        self.obs = obs if obs is not None else NULL_OBS
        if adapter is None:
            if calib_k is None:
                raise ValueError("need a fitted LowRankAdapter or calibration K")
            adapter = fit_adapter(calib_k, rank=cfg.rank)
        if adapter.rank != cfg.rank:
            raise ValueError(f"adapter rank {adapter.rank} != cfg.rank {cfg.rank}")
        self.adapter = adapter
        self._per_head_a = adapter.per_head  # [H_k, d, r], cached for the jit

        g = cfg.group_size
        self.max_groups = (cfg.max_seq + g - 1) // g
        self.cap_tokens = self.max_groups * g
        # only "kv" layers own disk-backed KV state; "window" layers keep
        # their last `window` tokens in a device ring, "state" layers a
        # recurrent state (hybrid support)
        self.layer_kinds = tuple(getattr(model, "layer_kinds", ("kv",) * model.n_layers))
        self.kv_layers = [i for i, k in enumerate(self.layer_kinds) if k == "kv"]
        self._kv_index = {layer: j for j, layer in enumerate(self.kv_layers)}
        self.window_layers = [i for i, k in enumerate(self.layer_kinds) if k == "window"]
        self._win_index = {layer: w for w, layer in enumerate(self.window_layers)}
        self.window = int(model.window) if self.window_layers else 0
        # the prefix cache holds every attention layer, in model order
        self.attn_layers = [i for i, k in enumerate(self.layer_kinds) if k != "state"]
        self._attn_index = {layer: c for c, layer in enumerate(self.attn_layers)}
        n_kv_layers = len(self.kv_layers)
        self.accountant = IOAccountant(cfg.disk_spec)
        if self.obs.enabled:
            # mirror every I/O charge into the registry inside the
            # accountant's lock: registry totals stay bit-equal to
            # IOAccountant.snapshot() even with worker threads charging
            self.accountant.bind_metrics(self.obs.registry)
            reg = self.obs.registry
            self._m_steps = reg.counter(
                "kvswap_engine_decode_steps_total", "decode steps executed")
            self._m_tokens = reg.counter(
                "kvswap_engine_decode_tokens_total",
                "tokens decoded (active rows per step)")
            self._m_admissions = reg.counter(
                "kvswap_engine_admissions_total",
                "prefills + per-slot admissions")
            self._m_prefill_tokens = reg.counter(
                "kvswap_engine_prefill_tokens_total",
                "prompt tokens computed by prefill (cached tokens excluded)")
            self._m_hist_pipe = reg.histogram(
                "kvswap_step_pipelined_seconds",
                "modeled layer-pipelined decode-step latency")
            self._m_hist_wall = reg.histogram(
                "kvswap_step_wall_seconds", "host time to dispatch a decode step")
        if cfg.compute not in hardware.COMPUTES:
            raise ValueError(f"unknown compute {cfg.compute!r}; "
                             f"expected one of {sorted(hardware.COMPUTES)}")
        self.compute_spec = hardware.COMPUTES[cfg.compute]
        self.store = KVDiskStore(
            n_layers=n_kv_layers, batch=batch, max_groups=self.max_groups,
            group_size=g, n_kv_heads=model.n_kv_heads, head_dim=model.head_dim,
            dtype=cfg.np_dtype, accountant=self.accountant,
            quant_bits=8 if cfg.kv_bits == 8 else 0,
        )
        # fault injection (docs/robustness.md): with a FaultPlan attached
        # the disk tier is wrapped in the FaultyDisk shim; faults=None keeps
        # the bare store — the unfaulted stack is untouched by construction.
        # Imported lazily so the production path never loads the package.
        self.faults = faults
        if faults is not None:
            from repro.faults import FaultyDisk

            self.store = FaultyDisk(self.store, faults)
        mk = lambda: ReuseBuffer(
            batch=batch, capacity=cfg.reuse_capacity, group_size=g,
            n_kv_heads=model.n_kv_heads, head_dim=model.head_dim, dtype=cfg.np_dtype,
        )
        self.reuse = [mk() for _ in range(n_kv_layers)]
        self.rolling = [
            RollingBuffer(batch=batch, group_size=g, n_kv_heads=model.n_kv_heads,
                          head_dim=model.head_dim, dtype=cfg.np_dtype)
            for _ in range(n_kv_layers)
        ]
        self.scheduler = ReadScheduler(max_gap=cfg.coalesce_gap)
        # host-RAM warm tier (victim cache) between reuse buffers and disk:
        # ONE tier for the whole engine — warm_budget_bytes is a global
        # budget across layers and rows.  None when disabled, so the
        # disabled path is literally the pre-tier code.  Imported lazily:
        # repro.tiers pulls repro.core.hardware, so a module-level import
        # here would make `import repro.tiers` circular.
        self.warm = None
        if cfg.warm_budget_bytes > 0:
            from repro.tiers import WarmTier

            self.warm = WarmTier(budget_bytes=cfg.warm_budget_bytes,
                                 compute=self.compute_spec,
                                 accountant=self.accountant,
                                 obs=self.obs)
            self.store.warm = self.warm
        # one retry policy shared by every manager (and publish): transient
        # disk faults are absorbed below the engine, surfacing only as
        # modeled stall seconds; exhaustion escalates as typed FetchFailed
        self._retry = RetryPolicy(max_attempts=IO_MAX_ATTEMPTS,
                                  backoff_base_s=IO_BACKOFF_S)
        self.managers = [
            KVCacheManager(store=self.store, reuse=self.reuse[j], rolling=self.rolling[j],
                           layer=j, scheduler=self.scheduler, warm=self.warm,
                           obs=self.obs, retry=self._retry)
            for j in range(n_kv_layers)
        ]
        self.prefetcher: PrefetchWorker | None = None
        if cfg.async_io:
            self.prefetcher = PrefetchWorker(
                self._fetch_table, n_threads=IO_THREADS,
                max_pending=max(4, 2 * max(n_kv_layers, 1)),
                accountant=self.accountant,
                obs=self.obs,
            )
        # prefetch-quality meter: always on (host-side set arithmetic, pure
        # observation) — its counts feed StepStats / summarize_steps, with
        # or without an obs handle
        self.quality = PrefetchQualityMeter()
        # recurrent state for non-KV (SSM / xLSTM) layers
        self.states: dict[int, object] = {}
        # windowed layers' rings [B, W, H_kv, d] in the engine dtype: the
        # token at position p in slot p % W, written in place by decode
        ring = (batch, self.window, model.n_kv_heads, model.head_dim)
        self._ring_k = [jnp.zeros(ring, cfg.dtype) for _ in self.window_layers]
        self._ring_v = [jnp.zeros(ring, cfg.dtype) for _ in self.window_layers]
        if self.obs.enabled and self.window_layers:
            self.obs.registry.gauge(
                "kvswap_window_ring_bytes",
                "device bytes the windowed layers' rings hold").set(
                sum(r.nbytes for r in self._ring_k + self._ring_v))
        # Preallocated compressed K cache, one per KV layer: [B, cap_tokens, r]
        self.k_lr = [
            jnp.zeros((batch, self.cap_tokens, cfg.rank), dtype=jnp.float32)
            for _ in range(n_kv_layers)
        ]
        # per-row request lifecycle (continuous batching): every row is an
        # independently admitted/retired slot; the lockstep prefill() path
        # simply sets all rows at once
        self.row_active = np.zeros(batch, dtype=bool)
        self.row_seq = np.zeros(batch, dtype=np.int64)    # tokens seen (incl. tail)
        self.row_valid = np.zeros(batch, dtype=np.int64)  # tokens in k_lr (n_groups·G)
        # runtime critical-group budget: starts at cfg.n_select and can be
        # lowered/restored by the serving degradation ladder (set_n_select)
        # without touching the frozen config
        self.n_select = cfg.n_select
        self.pred_cfg = PredictorConfig(
            group_size=g, n_select=cfg.n_select,
            n_heads=model.n_heads, n_kv_heads=model.n_kv_heads,
        )
        self.dims = hardware.ModelDims(
            d_model=model.d_model, n_heads=model.n_heads, n_kv_heads=model.n_kv_heads,
            head_dim=model.head_dim, d_ff=getattr(model, "d_ff", 4 * model.d_model),
        )
        self.step_log: list[StepStats] = []
        self.prefill_report: dict = {}
        self.admit_log: list[dict] = []   # one report per admit_row/prefill
        self._prompt_np: np.ndarray | None = None
        # device-resident decode state (built lazily at the first decode step
        # so prefill seeds the host buffers first); adapters without
        # gather_context fall back to the host-gather path
        self.device_resident = bool(cfg.device_resident
                                    and hasattr(model, "gather_context"))
        # device rolling tail: per layer, a fixed [B, G, H_kv, d] mirror of
        # the rolling buffer holding the last < G decoded tokens per row
        # (written in place by decode, still on device, never round-tripped;
        # per-row validity comes from RollingBuffer.fills)
        self._tail_k: list[jax.Array | None] = [None] * n_kv_layers
        self._tail_v: list[jax.Array | None] = [None] * n_kv_layers
        self._dev_ready = False
        self._h2d_step = 0
        self._step_active = np.zeros(batch, dtype=bool)

    # -- per-row lifecycle views ----------------------------------------
    @property
    def seq_len(self) -> int:
        """Longest row's token count — the lockstep view (uniform batches)."""
        return int(self.row_seq.max(initial=0))

    @property
    def valid_tokens(self) -> int:
        """Longest row's compressed-cache watermark (lockstep view)."""
        return int(self.row_valid.max(initial=0))

    # ------------------------------------------------------------------
    def _fetch_table(self, j: int, ids: np.ndarray, mask: np.ndarray):
        """The prefetch worker's unit of work: host-only group resolution."""
        return self.managers[j].fetch(ids, mask)

    # ------------------------------------------------------------------
    def metadata_bytes(self) -> dict:
        """In-memory footprint of KVSwap state (the paper's Fig. 3a metric)."""
        # logical = bytes holding *valid* compressed keys, summed over the
        # layers that own a k_lr — KV layers only (hybrid models' state
        # layers have none), not model.n_layers; rows count their own
        # watermark (continuous batching admits them at different lengths)
        klr = int(self.row_valid.sum()) * self.cfg.rank * 4
        klr_alloc = sum(int(np.prod(k.shape)) * 4 for k in self.k_lr)
        reuse = sum(r.nbytes for r in self.reuse)
        rolling = sum(r.nbytes for r in self.rolling)
        out = {
            "k_lr_logical": klr * len(self.kv_layers),
            "k_lr_alloc": klr_alloc,
            "reuse_buffer": reuse,
            "rolling_buffer": rolling,
            "total": klr_alloc + reuse + rolling,
        }
        if self.warm is not None:
            # warm tier: int8 slab payload + modeled index overhead, both
            # charged against warm_budget_bytes — the knob is auditable here
            out["warm_tier"] = self.warm.nbytes
            out["warm_tier_index"] = self.warm.index_nbytes
            out["warm_budget_bytes"] = self.warm.budget_bytes
            out["total"] += out["warm_tier"] + out["warm_tier_index"]
        if self.window_layers:
            # device memory, like the mirrors below
            out["window_ring"] = sum(r.nbytes for r in self._ring_k + self._ring_v)
        if any(r.device is not None for r in self.reuse):
            # the device mirrors double C's footprint (host copy + device
            # mirror); reported separately — it bounds *device* memory
            out["device_mirror"] = sum(
                r.device.nbytes for r in self.reuse if r.device is not None)
        return out

    # ------------------------------------------------------------------
    def _modeled_prefill_compute(self, n_new: int, n_ctx0: int,
                                 batch: int | None = None) -> float:
        """Modeled compute seconds to (chunked-)prefill ``n_new`` tokens."""
        return self.model.n_layers * hardware.prefill_layer_time(
            self.compute_spec, self.dims, n_new=n_new, n_ctx0=n_ctx0,
            batch=self.batch if batch is None else batch)

    def _finish_prefill_report(self, *, s: int, n_cached: int, tr, wall: float) -> None:
        """Modeled + measured prefill accounting (cold and warm paths).

        ``modeled_seconds`` charges restore reads, store writes and (chunked)
        compute sequentially — prefill is one pass, there is no layer
        pipeline to hide behind; ``modeled_cold_seconds`` prices the same
        prompt with zero cached tokens so callers can report the saving.
        """
        compute = self._modeled_prefill_compute(s - n_cached, n_cached)
        cold_compute = self._modeled_prefill_compute(s, 0)
        self.prefill_report = {
            "prompt_tokens": s,
            "cached_tokens": n_cached,
            "computed_tokens": s - n_cached,
            "restore_seconds": tr.read_seconds,
            "write_seconds": tr.write_seconds,
            "compute_seconds": compute,
            "modeled_seconds": tr.read_seconds + tr.write_seconds + compute,
            "modeled_cold_seconds": cold_compute + tr.write_seconds,
            "wall_seconds": wall,
        }
        self.admit_log.append(dict(self.prefill_report))
        self._obs_admission("prefill", self.prefill_report)

    def _obs_admission(self, name: str, rep: dict) -> None:
        """Admission span on both clocks + admission counters.  Advances the
        modeled-clock cursor by the admission's modeled seconds, so the next
        decode-step span starts where this one ends."""
        obs = self.obs
        if not obs.enabled:
            return
        t0, _ = obs.advance_model(rep["modeled_seconds"])
        obs.tracer.add(
            name, "engine-step", cat="admission",
            wall_t0=max(0.0, obs.tracer.now_wall() - rep["wall_seconds"]),
            wall_dur=rep["wall_seconds"],
            model_t0=t0, model_dur=rep["modeled_seconds"],
            args={k: rep[k] for k in ("prompt_tokens", "cached_tokens", "row")
                  if k in rep})
        self._m_admissions.inc()
        self._m_prefill_tokens.inc(rep["computed_tokens"])

    def _spill_prefill_layer(self, j: int, k_np: np.ndarray, v_np: np.ndarray,
                             k_dev: jax.Array, s: int) -> None:
        """Per-layer prefill spill shared by the cold and warm paths: write
        the full groups to disk, seed the rolling tail, append to ``k_lr``.
        One body so the two paths cannot drift (the warm path's bit-identity
        contract depends on them matching)."""
        g = self.cfg.group_size
        ng = s // g
        self.store.write_prefill(j, k_np, v_np)
        if s - ng * g:
            self.rolling[j].seed(k_np[:, ng * g :], v_np[:, ng * g :])
        if ng:
            rows = compress_k(k_dev[:, : ng * g].astype(jnp.float32), self.adapter)
            self.k_lr[j] = _klr_append(self.k_lr[j], rows, jnp.int32(0))

    def _seed_ring(self, layer: int, rows, k: np.ndarray, v: np.ndarray,
                   end: int) -> None:
        """Seed windowed ``layer``'s ring rows from the K/V ``[len(rows), n,
        H_kv, d]`` of the ``n`` tokens before position ``end``: the last
        ``window`` of them, each in its slot, the other slots cleared."""
        wl, w = self._win_index[layer], self.window
        n = min(k.shape[1], w)
        slots = np.arange(end - n, end) % w
        for i, bi in enumerate(rows):
            for ring, src in ((self._ring_k, k), (self._ring_v, v)):
                row = np.zeros(ring[wl].shape[1:], ring[wl].dtype)
                row[slots] = src[i, -n:]
                ring[wl] = _ring_row(ring[wl], jnp.int32(bi), jnp.asarray(row))

    def _window_tail(self, k_pre: np.ndarray, layer: int) -> np.ndarray:
        """The part of restored prefix K/V ``[..., n, H_kv, d]`` that
        ``layer`` attends: all of it, or a windowed layer's last ``window``
        tokens (the only ones a restore holds for it)."""
        return k_pre[..., -self.window:, :, :] if layer in self._win_index else k_pre

    def _window_covered(self, chain, n_blocks: int) -> bool:
        """Whether a restore of the chain's first ``n_blocks`` may seed the
        rings: every block ending within ``window`` tokens of their end
        holds its windowed layers' K/V."""
        if not self.window_layers or not n_blocks:
            return True
        ends = np.cumsum([m.n_tokens for m in chain[:n_blocks]])
        return all(m.window for m, e in zip(chain, ends) if e > ends[-1] - self.window)

    def _common_chains(self, cache, tokens_np: np.ndarray, s: int) -> list:
        """Each row's cached chain, cut to the longest prefix every row
        matches whose windowed blocks cover the window before its end (a
        restore that cannot seed the rings falls back to a shorter one)."""
        chains = [cache.match(t, max_tokens=s - 1) for t in tokens_np]
        n = min(len(ch) for ch in chains)
        while not all(self._window_covered(ch, n) for ch in chains):
            n -= 1
        return [ch[:n] for ch in chains]

    def _restore_read(self, cache, chain):
        """``cache.read_chain`` of a window-covered chain: the windowed
        layers' K/V only for its last ``window`` tokens."""
        return cache.read_chain(chain, [self._attn_index[i] for i in self.window_layers],
                                self.window)

    def _open_cache(self, cache) -> None:
        cache.open(n_layers=len(self.attn_layers), group_size=self.cfg.group_size,
                   n_kv_heads=self.model.n_kv_heads,
                   head_dim=self.model.head_dim, dtype=self.cfg.np_dtype)
        cache.use_accountant(self.accountant)
        cache.use_obs(self.obs)

    def prefill(self, tokens: np.ndarray) -> jax.Array:
        """Run full-attention prefill, spill KV to disk layer-by-layer, build
        the compressed K cache.  Returns last-position logits ``[B, V]``."""
        t0 = time.perf_counter()
        self._reset_device_state()   # mirrors rebuilt at first decode
        self._prompt_np = np.asarray(jax.device_get(tokens))
        tokens = jnp.asarray(tokens)
        b, s = tokens.shape
        if b != self.batch:
            raise ValueError(f"batch mismatch {b} != {self.batch}")
        for bi in range(self.batch):   # lockstep admission of every slot
            self._free_row(bi)
        g = self.cfg.group_size
        positions = jnp.arange(s)[None, :].repeat(b, axis=0)
        x = self.model.embed(self.params, tokens)
        with self.accountant.track() as tr:
            for layer in range(self.model.n_layers):
                if self.layer_kinds[layer] == "state":
                    x, st = self.model.prefill_state_block(self.params, layer, x, positions)
                    self.states[layer] = st
                    continue
                x, k, v = self.model.prefill_block(self.params, layer, x, positions)
                k_np = np.asarray(jax.device_get(k), dtype=self.cfg.np_dtype)
                v_np = np.asarray(jax.device_get(v), dtype=self.cfg.np_dtype)
                if layer in self._win_index:
                    self._seed_ring(layer, range(b), k_np, v_np, s)
                else:
                    self._spill_prefill_layer(self._kv_index[layer], k_np, v_np, k, s)
        self.row_valid[:] = (s // g) * g
        self.row_seq[:] = s
        self.row_active[:] = True
        logits = self.model.logits(self.params, x[:, -1])
        self._finish_prefill_report(s=s, n_cached=0, tr=tr,
                                    wall=time.perf_counter() - t0)
        return logits

    # -- persistent prefix cache (src/repro/cache/) ---------------------
    def prefill_cached(self, tokens: np.ndarray, cache) -> jax.Array:
        """Prefill through the cross-request prefix cache.

        Longest-prefix match the prompt against ``cache``
        (:class:`repro.cache.PrefixCache`), restore the matched blocks' KV
        groups straight into this engine's disk store, and run **only the
        uncached suffix** through the model (chunked prefill over restored
        prefix KV).  At least one token is always recomputed so the call
        still returns last-position logits.

        Bit-identity: the cache stores KV in the raw engine dtype, the
        restored prefix bytes equal what a cold prefill would have written,
        and the chunked suffix computes the same score rows as the full
        forward — so logits (and every decode step after) are bit-identical
        to :meth:`prefill` on the same prompt.  That contract holds for a
        lossless disk tier and dense MLP blocks; it degrades to
        approximately-equal when the stored KV is lossy (``kv_bits=8``
        republishes the dequantized int8 payload; a ``dtype`` narrower than
        the compute dtype rounds the restored K that rebuilds ``k_lr``) or
        when MoE capacity routing drops tokens (the suffix-only pass routes
        fewer tokens than the full forward did).

        The batch prefills in lockstep, so the usable split is the *common*
        cached prefix (minimum over rows) — the intended workload is batched
        requests sharing a system prompt / conversation head.  Hybrid models
        fall back to cold prefill: recurrent state lives outside the KV
        cache.  Windowed layers restore only their last ``window`` tokens,
        which is all their suffix attends and all their rings hold.
        """
        t0 = time.perf_counter()
        tokens_np = np.asarray(jax.device_get(tokens))
        b, s = tokens_np.shape
        if b != self.batch:
            raise ValueError(f"batch mismatch {b} != {self.batch}")
        # cold fallbacks: hybrid models keep recurrent state outside the KV
        # cache, and adapters predating the chunked-prefill protocol can
        # still publish/serve cold
        if ("state" in self.layer_kinds
                or not hasattr(self.model, "prefill_block_with_ctx")):
            return self.prefill(tokens_np)
        g = self.cfg.group_size
        self._open_cache(cache)
        chains = self._common_chains(cache, tokens_np, s)
        n_cached = sum(m.n_tokens for m in chains[0])
        if n_cached == 0:
            return self.prefill(tokens_np)
        self._reset_device_state()   # mirrors rebuilt at first decode
        for bi in range(self.batch):   # lockstep admission of every slot
            self._free_row(bi)

        with self.accountant.track() as tr:
            # identical rows (shared system prompts, padded clones) resolve
            # to the same chain — read each unique chain once.  A checksum
            # mismatch quarantines the bad block (and its descendants) inside
            # read_chain; re-match against the now-shorter cache and retry —
            # warm prefill degrades to a longer suffix, never to wrong KV.
            while True:
                uniq = {ch[-1].block_id: ch for ch in chains}
                for ch in uniq.values():
                    cache.pin(ch)
                try:
                    data = {key: self._restore_read(cache, ch) for key, ch in uniq.items()}
                    break
                except CorruptBlockError:
                    chains = self._common_chains(cache, tokens_np, s)
                    n_cached = sum(m.n_tokens for m in chains[0])
                    if n_cached == 0:
                        return self.prefill(tokens_np)
                finally:
                    for ch in uniq.values():
                        cache.unpin(ch)
            nl, hkv, hd = len(self.attn_layers), self.model.n_kv_heads, self.model.head_dim
            k_pre = np.empty((nl, b, n_cached, hkv, hd), dtype=self.cfg.np_dtype)
            v_pre = np.empty_like(k_pre)
            for bi, ch in enumerate(chains):
                k_pre[:, bi], v_pre[:, bi] = data[ch[-1].block_id]

            positions = jnp.arange(n_cached, s)[None, :].repeat(b, axis=0)
            x = self.model.embed(self.params, jnp.asarray(tokens_np[:, n_cached:]))
            ng = s // g
            for layer in range(self.model.n_layers):
                c = self._attn_index[layer]
                kp_np = self._window_tail(k_pre[c], layer)
                vp_np = self._window_tail(v_pre[c], layer)
                kp, vp = jnp.asarray(kp_np), jnp.asarray(vp_np)
                x, k_suf, v_suf = self.model.prefill_block_with_ctx(
                    self.params, layer, x, positions, kp, vp)
                k_np = np.concatenate(
                    [kp_np, np.asarray(jax.device_get(k_suf), dtype=self.cfg.np_dtype)], axis=1)
                v_np = np.concatenate(
                    [vp_np, np.asarray(jax.device_get(v_suf), dtype=self.cfg.np_dtype)], axis=1)
                if layer in self._win_index:
                    self._seed_ring(layer, range(b), k_np, v_np, s)
                    continue
                self._spill_prefill_layer(
                    self._kv_index[layer], k_np, v_np,
                    jnp.concatenate([kp, k_suf], axis=1), s)
        self.row_valid[:] = ng * g
        self.row_seq[:] = s
        self.row_active[:] = True
        self._prompt_np = tokens_np
        logits = self.model.logits(self.params, x[:, -1])
        self._finish_prefill_report(s=s, n_cached=n_cached, tr=tr,
                                    wall=time.perf_counter() - t0)
        return logits

    def _restore_prefix(self, cache, tokens_np: np.ndarray, s: int):
        """Longest *verified* cached prefix of one prompt: match → pin →
        read_chain, re-matching after a :class:`CorruptBlockError`
        (``read_chain`` quarantined the bad block and its descendants, so
        every retry sees a strictly shorter chain and the loop terminates).
        Returns ``(n_cached, k_pre, v_pre)`` — ``(0, None, None)`` when
        nothing restorable is left."""
        while True:
            chain = self._common_chains(cache, tokens_np[None], s)[0]
            n_cached = sum(m.n_tokens for m in chain)
            if not n_cached:
                return 0, None, None
            cache.pin(chain)
            try:
                k_pre, v_pre = self._restore_read(cache, chain)  # [layers, n_cached, hkv, d]
                return n_cached, k_pre, v_pre
            except CorruptBlockError:
                continue
            finally:
                cache.unpin(chain)

    # -- per-slot request lifecycle (continuous batching) ----------------
    def admit_row(self, bi: int, tokens: np.ndarray, cache=None) -> jax.Array:
        """Prefill one prompt into free slot ``bi`` while other slots keep
        decoding; returns the slot's last-position logits ``[V]``.

        The single-row analogue of :meth:`prefill` (and, with ``cache``, of
        :meth:`prefill_cached`): the prompt runs through the model as a
        batch-1 forward, its KV spills into row ``bi`` of the shared disk
        store, the rolling tail seeds row ``bi``, and the compressed K cache
        gets the row's groups — no other row's state is touched, so slots
        already mid-decode are unaffected.  With a
        :class:`~repro.cache.PrefixCache` attached the longest cached prefix
        is restored from the cache slab instead of recomputed (chunked
        suffix prefill, same bit-identity contract as
        :meth:`prefill_cached`).

        ``prefill_report`` (and ``admit_log``) record the admission's
        modeled seconds, which a serving session charges to its clock.
        """
        if self.row_active[bi]:
            raise RuntimeError(f"slot {bi} is busy; retire it first")
        if "state" in self.layer_kinds:
            raise ValueError("admit_row requires attention-only models "
                             "(recurrent state has no per-row lifecycle)")
        tokens_np = np.asarray(jax.device_get(tokens)).reshape(-1).astype(np.int64)
        s = int(tokens_np.shape[0])
        if s < 1:
            raise ValueError("empty prompt")
        if s > self.cap_tokens:
            raise RuntimeError("prompt exceeds KV capacity; raise cfg.max_seq")
        t0 = time.perf_counter()
        self._free_row(bi)
        g = self.cfg.group_size
        ng = s // g
        warm = (cache is not None
                and hasattr(self.model, "prefill_block_with_ctx"))
        n_cached = 0
        k_pre = v_pre = None
        try:
            with self.accountant.track() as tr:
                if warm:
                    self._open_cache(cache)
                    n_cached, k_pre, v_pre = self._restore_prefix(
                        cache, tokens_np, s)
                positions = jnp.arange(n_cached, s)[None, :]
                x = self.model.embed(
                    self.params, jnp.asarray(tokens_np[None, n_cached:]))
                for layer in range(self.model.n_layers):
                    if n_cached:
                        c = self._attn_index[layer]
                        kp_np = self._window_tail(k_pre[c], layer)
                        vp_np = self._window_tail(v_pre[c], layer)
                        kp, vp = jnp.asarray(kp_np[None]), jnp.asarray(vp_np[None])
                        x, k_suf, v_suf = self.model.prefill_block_with_ctx(
                            self.params, layer, x, positions, kp, vp)
                        k_dev = jnp.concatenate([kp, k_suf], axis=1)
                        k_np = np.concatenate(
                            [kp_np, np.asarray(jax.device_get(k_suf[0]),
                                               dtype=self.cfg.np_dtype)], axis=0)
                        v_np = np.concatenate(
                            [vp_np, np.asarray(jax.device_get(v_suf[0]),
                                               dtype=self.cfg.np_dtype)], axis=0)
                    else:
                        x, k, v = self.model.prefill_block(self.params, layer, x, positions)
                        k_dev = k
                        k_np = np.asarray(jax.device_get(k[0]), dtype=self.cfg.np_dtype)
                        v_np = np.asarray(jax.device_get(v[0]), dtype=self.cfg.np_dtype)
                    if layer in self._win_index:
                        self._seed_ring(layer, [bi], k_np[None], v_np[None], s)
                        continue
                    j = self._kv_index[layer]
                    self.store.write_prefill_row(j, bi, k_np, v_np)
                    if s - ng * g:
                        self.rolling[j].seed_row(bi, k_np[ng * g:], v_np[ng * g:])
                    if ng:
                        rows = compress_k(k_dev[:, : ng * g].astype(jnp.float32),
                                          self.adapter)
                        self.k_lr[j] = _klr_append_row(
                            self.k_lr[j], rows, jnp.int32(bi), jnp.int32(0))
                    if self._dev_ready:
                        # seed the device rolling mirror's row from the host tail
                        self._tail_k[j] = self._tail_k[j].at[bi].set(
                            jnp.asarray(self.rolling[j].k[bi]).astype(self._tail_k[j].dtype))
                        self._tail_v[j] = self._tail_v[j].at[bi].set(
                            jnp.asarray(self.rolling[j].v[bi]).astype(self._tail_v[j].dtype))
        except StorageFault:
            # failure atomicity: a half-admitted slot (some layers written,
            # some not) must not look admissible or decodeable — roll it all
            # the way back to "free" and let the caller fail the request
            self._free_row(bi)
            self.row_active[bi] = False
            raise
        self.row_seq[bi] = s
        self.row_valid[bi] = ng * g
        self.row_active[bi] = True
        logits = self.model.logits(self.params, x[:, -1])[0]
        compute = self._modeled_prefill_compute(s - n_cached, n_cached, batch=1)
        cold = self._modeled_prefill_compute(s, 0, batch=1)
        self.prefill_report = {
            "prompt_tokens": s,
            "cached_tokens": n_cached,
            "computed_tokens": s - n_cached,
            "restore_seconds": tr.read_seconds,
            "write_seconds": tr.write_seconds,
            "compute_seconds": compute,
            "modeled_seconds": tr.read_seconds + tr.write_seconds + compute,
            "modeled_cold_seconds": cold + tr.write_seconds,
            "wall_seconds": time.perf_counter() - t0,
            "row": bi,
        }
        self.admit_log.append(dict(self.prefill_report))
        self._obs_admission("admit_row", self.prefill_report)
        return logits

    def retire_row(self, bi: int) -> None:
        """End slot ``bi``'s request and free everything it held: mapping
        addressing (reuse slot table), reuse-buffer slots, rolling tail,
        device-mirror reachability, disk extents, and the compressed-cache
        watermark.  The slot becomes admissible immediately; publishing to a
        prefix cache (if any) is the *caller's* job and must happen before
        retirement (the disk extents are recycled here)."""
        self.row_active[bi] = False
        self._free_row(bi)

    def deactivate_row(self, bi: int) -> None:
        """Mask slot ``bi`` out of decoding without freeing its state (stop
        tokens: a stopped row issues no reads and charges no time, but its
        KV stays publishable until :meth:`retire_row`)."""
        self.row_active[bi] = False

    def reactivate_row(self, bi: int) -> None:
        """Undo :meth:`deactivate_row`: the slot resumes decoding from
        exactly where it stopped (its KV, tail, and selection history were
        never freed).  Only valid on a slot holding a live request."""
        if self.row_seq[bi] == 0:
            raise RuntimeError(f"slot {bi} holds no request; admit one first")
        self.row_active[bi] = True

    def set_n_select(self, n: int) -> int:
        """Set the *runtime* critical-group budget (degradation ladder knob).

        Bounded by ``[1, cfg.n_select]`` — the device gather mirror and the
        reuse buffer were sized for ``cfg.n_select`` at construction, so the
        budget can shrink under load (fewer groups fetched per step → less
        I/O per step) and recover back up, but never exceed its capacity.
        Takes effect on the next :meth:`decode_step`; changing it changes
        which groups attend, so outputs are only bit-identical to a run
        that made the same changes at the same steps.  Returns the clamped
        value actually in effect.
        """
        self.n_select = max(1, min(int(n), self.cfg.n_select))
        return self.n_select

    def _free_row(self, bi: int) -> None:
        for j in range(len(self.kv_layers)):
            self.managers[j].free_row(bi)
        self.store.free_row(bi)
        for rings in (self._ring_k, self._ring_v):
            for wl, ring in enumerate(rings):
                rings[wl] = _ring_row(ring, jnp.int32(bi),
                                      jnp.zeros(ring.shape[1:], ring.dtype))
        # forget the slot's selection history: a recycled slot's first step
        # must not score against the previous tenant's selections
        self.quality.clear_row(bi)
        self.row_seq[bi] = 0
        self.row_valid[bi] = 0

    def publish(self, cache, tokens: np.ndarray | Sequence[np.ndarray] | None = None,
                rows: Sequence[int] | None = None,
                save: bool = True) -> "PublishResult":
        """Publish this request's KV into ``cache`` (end-of-request hook).

        ``tokens`` is the per-row served token history (prompt + every token
        fed to :meth:`decode_step`); it defaults to the prefill prompt, which
        is always safe — prompt KV was written by full-attention prefill, so
        later warm prefills restore exactly what a cold one would compute.
        Passing the full history additionally shares *generated* KV with
        follow-up turns (those entries are as-decoded under sparse attention,
        the same approximation this engine itself continues with).

        Blocks are published root-first and deduplicated by content hash;
        returns a :class:`PublishResult` — an ``int`` counting newly resident
        blocks, whose ``.heads`` maps each published row to the deepest
        resident block id of its chain (the handle a disagg prefill ticket
        carries so the decode side can restore without re-hashing the
        prompt).  ``save=False`` defers the manifest write — per-request
        publishers (the serving session retires rows one at a time) save
        once at drain instead of rewriting the manifest per retirement.

        Windowed layers' K/V come from the rings, which hold each row's last
        ``window`` tokens: a block published older than that carries only
        the full layers' K/V (``window=False`` in the cache), and a resident
        block that lacked them is rewritten when a publish has them.
        """
        if "state" in self.layer_kinds:
            return PublishResult(0, {})
        if tokens is None:
            tokens = self._prompt_np
        if tokens is None:        # nothing prefilled yet → nothing to publish
            return PublishResult(0, {})
        g = self.cfg.group_size
        self._open_cache(cache)
        bt = cache.cfg.block_tokens
        hkv, hd = self.model.n_kv_heads, self.model.head_dim
        published = 0
        heads: dict[int, str | None] = {}
        bg = bt // g
        for bi in (rows if rows is not None else range(self.batch)):
            toks = np.asarray(tokens[bi]).reshape(-1)
            on_disk = int(self.store.n_groups[:, bi].min()) * g
            usable = min(len(toks), on_disk)
            chain = chain_blocks(toks[:usable], bt)
            # first token whose windowed K/V the rings still hold
            held = int(self.row_seq[bi]) - self.window if self.window_layers else usable
            # resident blocks form rooted chains, so the missing blocks are
            # a contiguous suffix: touch the resident prefix, then read the
            # whole missing range as ONE sequential run per layer
            n_res = 0
            for blk in chain:
                if not cache.contains(blk.block_id) or (
                        blk.index * bt >= held and not cache.holds_window(blk.block_id)):
                    break
                cache.touch(blk.block_id)
                n_res += 1
            missing = chain[n_res:]
            n_ok = n_res
            if missing:
                g0 = missing[0].index * bg
                ngr = len(missing) * bg
                k = np.zeros((len(self.attn_layers), ngr, g, hkv, hd), dtype=self.cfg.np_dtype)
                v = np.zeros_like(k)
                for j, layer in enumerate(self.kv_layers):
                    # retried like a decode fetch: a transient read error must
                    # not fail the request at the finish line (publishing is
                    # best-effort, but a retry is cheaper than losing the chain)
                    c = self._attn_index[layer]
                    k[c], v[c] = self.managers[j].read_run_with_retry(
                        bi, ReadRun(g0, ngr, tuple(range(g0, g0 + ngr))))
                if self.window_layers:
                    pos = g0 * g + np.arange(ngr * g)
                    ok = pos >= held
                    ring_k, ring_v = jax.device_get(
                        ([r[bi] for r in self._ring_k], [r[bi] for r in self._ring_v]))
                    for layer, rk, rv in zip(self.window_layers, ring_k, ring_v):
                        c = self._attn_index[layer]
                        for dst, ring in ((k[c], rk), (v[c], rv)):
                            flat = dst.reshape(ngr * g, hkv, hd)
                            flat[ok] = ring[pos[ok] % self.window]
                for blk in missing:
                    off = (blk.index * bg) - g0
                    if not cache.put_block(blk, k[:, off:off + bg], v[:, off:off + bg],
                                           window=blk.index * bt >= held):
                        break   # budget exhausted by pinned blocks; keep the chain rooted
                    published += 1
                    n_ok += 1
            heads[int(bi)] = chain[n_ok - 1].block_id if n_ok else None
        if save:
            cache.save()
        return PublishResult(published, heads)

    # ------------------------------------------------------------------
    def decode_step(self, token_ids: np.ndarray) -> jax.Array:
        """Decode one token per sequence; returns logits ``[B, V]``.

        Sync and async modes share every numeric call (prediction, gather,
        block compute) on identical inputs, so their outputs are
        bit-identical; async mode only moves the disk reads off the critical
        path (§3.3's overlap).

        Only **active** rows decode: inactive (retired/stopped/empty) slots
        select no groups, fetch nothing, append nothing, and charge no
        modeled time — their logits rows are garbage the caller must ignore.
        Token values for inactive rows are ignored.  A row's numeric stream
        depends only on its own state, so tokens match the lockstep path for
        identical arrival patterns bit for bit."""
        active = self.row_active.copy()
        n_active = int(active.sum())
        if n_active == 0:
            raise RuntimeError("no active rows (prefill or admit_row first)")
        if (self.row_seq[active] + 1 > self.cap_tokens).any():
            raise RuntimeError("KV capacity exceeded; raise cfg.max_seq")
        obs = self.obs
        with obs.phase("decode_step", step=len(self.step_log)):
            t0 = time.perf_counter()
            with obs.phase("step_prologue"):
                if self.device_resident:
                    self._ensure_device_state()
                warm_bytes0 = self.accountant.warm_bytes
                self._h2d_step = 0
                self._step_active = active
                self.quality.begin_step()
                b = self.batch
                if n_active == b:
                    tok = jnp.asarray(token_ids).reshape(b, 1)   # stays on device
                else:
                    tok = jnp.asarray(
                        np.where(active, np.asarray(token_ids).reshape(b), 0)
                    ).reshape(b, 1)
                pos = jnp.asarray(self.row_seq.astype(np.int32))
                x = self.model.embed(self.params, tok)[:, 0]
                valid = jnp.asarray(self.row_valid.astype(np.int32))

            t_compute: list[float] = []
            t_io: list[float] = []
            # (KV layer, row) groups completed this step, flushed at its end
            done = np.zeros((len(self.kv_layers), b), dtype=bool)
            if self.prefetcher is not None:
                x, io_wait = self._layers_async(x, pos, valid, t_compute, t_io, done)
            else:
                x, io_wait = self._layers_sync(x, pos, valid, t_compute, t_io, done)

            n_flush = int(done.sum())
            if n_flush:
                with obs.phase("spill", groups=n_flush):
                    logits = self._flush(done, valid, x)
            else:
                logits = self.model.logits(self.params, x)
            self.row_seq[active] += 1

            stats = StepStats()
            stats.io_seconds = sum(t_io)
            stats.compute_seconds = sum(t_compute)
            stats.pipelined_seconds = self._pipeline_latency(t_compute, t_io)
            snap = self.accountant.snapshot()
            stats.io_bytes = snap["read_bytes"]
            stats.io_requests = snap["read_requests"]
            stats.warm_bytes = snap["warm_bytes"] - warm_bytes0
            stats.io_wait_seconds = io_wait
            stats.h2d_bytes = self._h2d_step
            stats.active_rows = n_active
            qc = self.quality.finish_step()
            stats.pred_shared_groups = qc.shared_groups
            stats.pred_prev_groups = qc.prev_groups
            stats.pred_cur_groups = qc.cur_groups
            stats.stale_groups = qc.stale_groups
            stats.resident_groups = qc.resident_groups
            stats.flushed_groups = n_flush
            # host time of the step: the logits are not waited for
            stats.wall_seconds = time.perf_counter() - t0
            self.step_log.append(stats)
            if obs.enabled:
                self._obs_step(stats, t_compute, t_io)
        return logits

    def _flush(self, done: np.ndarray, valid: jax.Array, x: jax.Array) -> jax.Array:
        """Flush every group the step completed; returns the step's logits.

        One program appends the completed groups' compressed keys to
        ``k_lr`` and casts them for the disk; the head is dispatched behind
        it, so the device computes the logits while the host waits on the
        one download and writes the groups, layer by layer and row by row.
        The host path wrote its groups during the layer loop; its rolling
        buffers still hold them, and reach the program in one upload."""
        if self.device_resident:
            groups_k, groups_v = tuple(self._tail_k), tuple(self._tail_v)
        else:
            groups_k = jnp.asarray(np.stack([rb.k for rb in self.rolling]))
            groups_v = ()
            self._h2d_step += groups_k.nbytes
        kv, k_lr = _flush_groups(tuple(self.k_lr), self.adapter.a, jnp.asarray(done),
                                 valid, groups_k, groups_v, dtype=self.cfg.np_dtype)
        self.k_lr = list(k_lr)
        logits = self.model.logits(self.params, x)
        if kv is not None:
            k_np, v_np = jax.device_get(kv)
            for j, bi in zip(*np.nonzero(done)):
                self.managers[j].spill_group_row(int(bi), k_np[j, bi], v_np[j, bi])
        self.row_valid[done.any(axis=0)] += self.cfg.group_size
        return logits

    def _obs_step(self, stats: StepStats, t_compute: Sequence[float],
                  t_io: Sequence[float]) -> None:
        """Decode-step spans on both clocks + per-step metrics.

        The per-layer modeled lanes replay the :meth:`_pipeline_latency`
        recurrence, so the ``compute`` and ``io`` bars land exactly where
        the latency model says they do — layer *i+1*'s I/O bar visibly
        hiding under layer *i*'s compute bar is the paper's §3.3 overlap,
        straight from the trace.  The ``decode_step`` span name on the
        ``engine-step`` lane is load-bearing: :func:`repro.obs.report.
        overlap_summary` filters on it to exclude admission spans.
        """
        obs = self.obs
        tr = obs.tracer
        t0, _ = obs.advance_model(stats.pipelined_seconds)
        tr.add("decode_step", "engine-step", cat="decode",
               wall_t0=max(0.0, tr.now_wall() - stats.wall_seconds),
               wall_dur=stats.wall_seconds,
               model_t0=t0, model_dur=stats.pipelined_seconds,
               args={"active_rows": stats.active_rows,
                     "io_seconds": stats.io_seconds,
                     "compute_seconds": stats.compute_seconds,
                     "io_wait_seconds": stats.io_wait_seconds})
        L = len(t_compute)
        t = t0
        if t_io:
            if t_io[0] > 0:
                tr.add("io L0", "io", cat="io", model_t0=t, model_dur=t_io[0])
            t += t_io[0]
        for i in range(L):
            nxt = t_io[i + 1] if i + 1 < L else 0.0
            if t_compute[i] > 0:
                tr.add(f"compute L{i}", "compute", cat="compute",
                       model_t0=t, model_dur=t_compute[i])
            if nxt > 0:
                tr.add(f"io L{i + 1}", "io", cat="io",
                       model_t0=t, model_dur=nxt)
            t += max(t_compute[i], nxt)
        self._m_steps.inc()
        self._m_tokens.inc(stats.active_rows)
        self._m_hist_pipe.observe(stats.pipelined_seconds)
        self._m_hist_wall.observe(stats.wall_seconds)

    def _reset_device_state(self) -> None:
        """Drop the device mirrors and tails (called on re-prefill) so stale
        device memory is released — and not silently resident while
        unreported — during the prefill peak; the first decode step after
        rebuilds them from the fresh host state."""
        self._dev_ready = False
        for j in range(len(self.kv_layers)):
            self.reuse[j].device = None
            self._tail_k[j] = None
            self._tail_v[j] = None

    def _ensure_device_state(self) -> None:
        """Build the per-layer device mirrors at the first decode step: the
        reuse buffer's slot storage (usually empty) and the rolling tail the
        prefill seeded.  One upload per request; every later step ships only
        fetch misses."""
        if self._dev_ready:
            return
        for j in range(len(self.kv_layers)):
            mirror = self.reuse[j].attach_device_mirror()
            if j == 0:   # jit cache is shared across layers (same shapes)
                mirror.prewarm(self.batch * self.cfg.n_select)
            # whole [B, G] rolling mirror; per-row validity lives in
            # RollingBuffer.fills (stale columns are masked at gather)
            self._tail_k[j] = jnp.asarray(self.rolling[j].k).astype(jnp.float32)
            self._tail_v[j] = jnp.asarray(self.rolling[j].v).astype(jnp.float32)
        self._dev_ready = True

    # -- per-layer pieces shared by both modes --------------------------
    def _predict_for(self, layer: int, j: int, pred_src: jax.Array, pos: jax.Array,
                     valid: jax.Array) -> tuple[np.ndarray, np.ndarray]:
        """Score + select layer ``layer``'s critical groups from ``pred_src``.

        The prediction itself is one fused dispatch (:meth:`_predict`); the
        device ``(ids, mask)`` pair is pulled to host in a single transfer
        here, just before the fetch needs it.  Inactive rows are masked out
        on host — they select no groups, so the fetch issues no disk reads
        for them (the active-row contract of continuous batching)."""
        with self.obs.phase("predict", layer=layer):
            q_pred = self.model.predict_query(self.params, layer, pred_src, pos)
            selected = self._predict(j, q_pred, valid)
            # the host blocks here until the device has selected
            with self.obs.phase("select_pull", layer=layer):
                ids, mask = jax.device_get(selected)
            masked = mask & self._step_active[:, None]
            # score the selection for prefetch quality (main thread in both
            # modes: async predicts before submitting the fetch, so layer j's
            # reuse buffer is quiescent here)
            self.quality.observe(layer, ids, masked, self.reuse[j])
        return ids, masked

    def _state_layer(self, layer: int, x: jax.Array, pos: jax.Array,
                     t_compute: list[float]) -> jax.Array:
        x, self.states[layer] = self.model.decode_state_block(
            self.params, layer, x, pos, self.states[layer]
        )
        t_compute.append(
            hardware.decode_layer_time(self.compute_spec, self.dims, n_ctx=0,
                                       batch=int(self._step_active.sum()))
        )
        return x

    def _window_layer(self, layer: int, x: jax.Array, pos: jax.Array,
                      t_compute: list[float]) -> jax.Array:
        """A windowed layer: one dispatch over its device ring, which takes
        the token's K/V in place; nothing reaches the host or the disk."""
        wl = self._win_index[layer]
        with self.obs.phase("window", layer=layer) as ph:
            x, self._ring_k[wl], self._ring_v[wl] = self.model.decode_window_block(
                self.params, layer, x, pos, self._ring_k[wl], self._ring_v[wl],
                jnp.asarray(self._step_active))
            t_compute.append(hardware.decode_layer_time(
                self.compute_spec, self.dims, n_ctx=self.window,
                batch=int(self._step_active.sum())))
            ph.note(modeled_compute_s=t_compute[-1])
        return x

    def _kv_layer(self, layer: int, j: int, x: jax.Array, pos: jax.Array, table,
                  t_compute: list[float], done: np.ndarray) -> jax.Array:
        if self.device_resident:
            return self._kv_layer_device(layer, j, x, pos, table, t_compute, done)
        return self._kv_layer_host(layer, j, x, pos, table, t_compute, done)

    def _kv_layer_host(self, layer: int, j: int, x: jax.Array, pos: jax.Array,
                       table, t_compute: list[float], done: np.ndarray) -> jax.Array:
        """Seed behavior (the A/B control): host concat + full upload."""
        cfg = self.cfg
        with self.obs.phase("attn", layer=layer) as ph:
            k_ctx, v_ctx, tok_mask, _ = self.managers[j].gather(table)
            self._h2d_step += k_ctx.nbytes + v_ctx.nbytes
            x, k_new, v_new = self.model.decode_block(
                self.params, layer, x, pos,
                jnp.asarray(k_ctx), jnp.asarray(v_ctx), jnp.asarray(tok_mask),
                use_pallas=cfg.use_pallas,
            )
            self._charge_layer_compute(j, k_ctx.shape[1] + 1, t_compute)
            ph.note(modeled_compute_s=t_compute[-1])
        with self.obs.phase("spill", layer=layer):
            completed = self.managers[j].append_token_rows(
                np.asarray(jax.device_get(k_new), dtype=cfg.np_dtype),
                np.asarray(jax.device_get(v_new), dtype=cfg.np_dtype),
                self._step_active,
            )
            for bi, _, _ in completed:
                done[j, bi] = True
        return x

    def _kv_layer_device(self, layer: int, j: int, x: jax.Array, pos: jax.Array,
                         table, t_compute: list[float], done: np.ndarray) -> jax.Array:
        """Device-resident hot path: only fetch misses cross host→device.

        The reuse mirror is brought up to date with one scatter of the
        fetch's ``new_groups`` delta, the context is gathered on device by
        the step's slot permutation, and the freshly decoded ``k_new/v_new``
        stay on device in a rolling mirror until the group completes; the
        row is then marked in ``done`` for the step-end flush (:meth:`_flush`).
        Feeds the *same* compiled ``decode_block`` as the host path with
        bit-identical inputs, so tokens match the control exactly.
        """
        cfg = self.cfg
        g = cfg.group_size
        mgr = self.managers[j]
        with self.obs.phase("mirror_upload", layer=layer):
            self._h2d_step += mgr.sync_device(table)
        with self.obs.phase("attn", layer=layer) as ph:
            mirror = self.reuse[j].device
            k_ctx, v_ctx, tok_mask = self.model.gather_context(
                mirror.k, mirror.v, jnp.asarray(table.slots),
                self._tail_k[j], self._tail_v[j],
                jnp.asarray(table.rolling_fill.astype(np.int32)))
            # overflow groups that couldn't enter the pinned-full reuse buffer
            # (slots == -2) are staged on host: upload transiently and overwrite
            # their gathered rows (rare — C smaller than the step's working set).
            # All staged rows go in ONE batched update so the context is copied
            # once, not once per staged group.
            if table.staged:
                rows_b: list[int] = []
                rows_t: list[int] = []
                pay_k: list[np.ndarray] = []
                pay_v: list[np.ndarray] = []
                for (bi, gid), kv in table.staged.items():
                    self._h2d_step += kv.nbytes
                    for mi in np.nonzero((table.group_ids[bi] == gid)
                                         & (table.slots[bi] == -2))[0]:
                        rows_b.extend([bi] * g)
                        rows_t.extend(range(int(mi) * g, (int(mi) + 1) * g))
                        pay_k.append(kv[:, 0])
                        pay_v.append(kv[:, 1])
                if rows_b:
                    bb = jnp.asarray(np.asarray(rows_b))
                    tt = jnp.asarray(np.asarray(rows_t))
                    k_ctx = k_ctx.at[bb, tt].set(jnp.asarray(np.concatenate(pay_k)))
                    v_ctx = v_ctx.at[bb, tt].set(jnp.asarray(np.concatenate(pay_v)))
            x, k_new, v_new = self.model.decode_block(
                self.params, layer, x, pos, k_ctx, v_ctx, tok_mask,
                use_pallas=cfg.use_pallas)
            # scatter each active row's fresh token into its own tail position
            # (rows sit at different fills under continuous batching)
            act = jnp.asarray(self._step_active)
            fidx = jnp.asarray(mgr.rolling.fills.astype(np.int32))
            self._tail_k[j] = _tail_write(self._tail_k[j], k_new, fidx, act)
            self._tail_v[j] = _tail_write(self._tail_v[j], v_new, fidx, act)
            done[j, mgr.rolling.advance_rows(self._step_active)] = True
            self._charge_layer_compute(j, k_ctx.shape[1] + 1, t_compute)
            ph.note(modeled_compute_s=t_compute[-1])
        return x

    def _charge_layer_compute(self, j: int, n_ctx: int,
                              t_compute: list[float]) -> None:
        # only active rows charge modeled time (retired/empty slots are free)
        t_compute.append(
            hardware.decode_layer_time(
                self.compute_spec, self.dims, n_ctx=n_ctx,
                batch=int(self._step_active.sum()),
                rank=self.cfg.rank, n_lr_tokens=self.valid_tokens,
            )
        )

    # -- synchronous path ------------------------------------------------
    def _layers_sync(self, x, pos, valid, t_compute, t_io, done):
        """Seed behavior: predict + fetch inline, on the critical path."""
        io_wait = 0.0
        x_prev = x
        for layer in range(self.model.n_layers):
            kind = self.layer_kinds[layer]
            if kind != "kv":
                x_prev = x
                x = (self._state_layer if kind == "state" else self._window_layer)(
                    layer, x, pos, t_compute)
                t_io.append(0.0)
                continue
            j = self._kv_index[layer]
            pred_src = x if (self.cfg.predict_from == "self" or layer == 0) else x_prev
            ids, mask = self._predict_for(layer, j, pred_src, pos, valid)
            with self.obs.phase("fetch", layer=layer) as ph:
                w0 = time.perf_counter()
                with self.accountant.track() as tr:
                    table = self.managers[j].fetch(ids, mask)
                io_wait += time.perf_counter() - w0
                # the fetch-serve lane: disk reads plus warm-tier memcpy+dequant
                t_io.append(tr.read_seconds + tr.warm_seconds)
                ph.note(modeled_io_s=t_io[-1], read_bytes=tr.read_bytes,
                        warm_bytes=tr.warm_bytes)
            x_prev = x
            x = self._kv_layer(layer, j, x, pos, table, t_compute, done)
        return x, io_wait

    # -- asynchronous pipeline (§3.3 / §3.4) ----------------------------
    def _layers_async(self, x, pos, valid, t_compute, t_io, done):
        """Issue layer *i+1*'s fetch as soon as its prediction source exists.

        With ``predict_from="prev"``, layer *L* is scored from layer *L−1*'s
        input — which is in hand *before* layer *L−1* computes, so the fetch
        rides the worker while compute proceeds.  ``predict_from="self"``
        degenerates to issue-then-wait (no overlap), matching the paper's
        argument for cross-layer prediction.
        """
        # source-layer index → kv layers predicted from that layer's input
        issue_at: dict[int, list[int]] = {}
        for L in self.kv_layers:
            src = L if (self.cfg.predict_from == "self" or L == 0) else L - 1
            issue_at.setdefault(src, []).append(L)
        buf = DoubleBuffer(depth=2)
        io_wait = 0.0
        try:
            for layer in range(self.model.n_layers):
                # `x` is the input to `layer` here: stage every kv layer
                # whose prediction source this is (the sync path's x_prev)
                for L in issue_at.get(layer, ()):
                    jj = self._kv_index[L]
                    ids, mask = self._predict_for(L, jj, x, pos, valid)
                    buf.stage(jj, self.prefetcher.submit(jj, ids, mask))
                kind = self.layer_kinds[layer]
                if kind != "kv":
                    x = (self._state_layer if kind == "state" else self._window_layer)(
                        layer, x, pos, t_compute)
                    t_io.append(0.0)
                    continue
                j = self._kv_index[layer]
                # the wall time *exposed* by this layer's fetch — the
                # worker records the fetch itself on its own lane
                with self.obs.phase("fetch_wait", layer=layer) as ph:
                    w0 = time.perf_counter()
                    res = buf.take(j)
                    io_wait += time.perf_counter() - w0
                    t_io.append(res.io_seconds)
                    ph.note(modeled_io_s=res.io_seconds)
                x = self._kv_layer(layer, j, x, pos, res.table, t_compute, done)
        except BaseException:
            buf.drain()   # never leave staged futures behind on an error
            raise
        return x, io_wait

    def _predict(self, layer: int, q_pred: jax.Array, valid: jax.Array):
        """Grouped critical-KV prediction against the compressed K cache.

        One fused dispatch (``lowrank_queries → token_scores → group_scores
        → select_groups`` under a single jit; Pallas scoring kernel when
        ``use_pallas``), returning device ``(ids, mask)``.  Both engine
        paths (``device_resident`` on/off) share this implementation, which
        is part of what keeps their decoded tokens bit-identical.  ``valid``
        is the per-row ``[B]`` compressed-cache watermark — rows admitted at
        different lengths (continuous batching) mask their own tails.
        """
        q32 = q_pred.astype(jnp.float32)
        if self.cfg.use_pallas:
            from repro.kernels import fused_predict_pallas

            return fused_predict_pallas(
                q32, self._per_head_a, self.k_lr[layer], valid,
                group_size=self.cfg.group_size, n_select=self.n_select)
        from repro.core.predictor import fused_predict

        return fused_predict(
            q32, self._per_head_a, self.k_lr[layer], valid,
            group_size=self.cfg.group_size, n_select=self.n_select)

    @staticmethod
    def _pipeline_latency(t_compute: Sequence[float], t_io: Sequence[float]) -> float:
        """Layer-pipelined step latency: I/O for layer i+1 overlaps compute of
        layer i; layer 0's I/O is exposed (§3.3 'online prediction')."""
        L = len(t_compute)
        lat = t_io[0] if t_io else 0.0
        for i in range(L):
            nxt_io = t_io[i + 1] if i + 1 < L else 0.0
            lat += max(t_compute[i], nxt_io)
        return lat

    # ------------------------------------------------------------------
    def generate(self, prompt: np.ndarray, n_new: int, *, greedy: bool = True,
                 rng: np.random.Generator | None = None,
                 stop_ids: Sequence[int] = ()) -> np.ndarray:
        """Prefill + ``n_new`` decode steps.  Returns ``[B, n_new]`` tokens.

        Sampling is jitted and the drawn ids stay on device between steps:
        greedy is one ``argmax`` dispatch, non-greedy a single vectorized
        ``jax.random.categorical`` draw over the whole batch
        (:func:`repro.serving.sampling.make_sampler` — no per-row host
        softmax loop).  ``rng`` only seeds the JAX key, keeping the old
        signature; the generated ``[B, n_new]`` block is pulled to host once
        at the end.

        ``stop_ids``: per-row EOS handling.  A row that emits a stop token is
        **masked, not decoded-and-truncated** — it is deactivated on the spot
        (no further disk reads, no modeled time) and its remaining positions
        repeat the stop token; ``last_stop_mask`` reports which rows stopped
        early.  When every row has stopped the loop exits.
        """
        from repro.serving import sampling as _sampling

        logits = self.prefill(prompt)
        if greedy:
            sample = _sampling.greedy_device
        else:
            seed = 0 if rng is None else int(rng.integers(0, 2**31 - 1))
            sample = _sampling.make_sampler(seed=seed, device=True)
        stop_set = np.asarray(sorted({int(t) for t in stop_ids}), dtype=np.int64)
        stopped = np.zeros(self.batch, dtype=bool)
        self.last_stop_mask = stopped
        if not stop_set.size:   # fast path: drawn ids stay on device
            out_dev = []
            for _ in range(n_new):
                nxt = sample(logits)
                out_dev.append(nxt)
                logits = self.decode_step(nxt)
            return np.asarray(jnp.stack(out_dev, axis=1))
        stop_tok = np.zeros(self.batch, dtype=np.int64)
        out: list[np.ndarray] = []
        for step in range(n_new):
            nxt = np.asarray(sample(logits)).astype(np.int64)
            nxt = np.where(stopped, stop_tok, nxt)         # frozen rows repeat
            newly = np.isin(nxt, stop_set) & ~stopped
            for bi in np.flatnonzero(newly):
                self.deactivate_row(bi)
            stop_tok = np.where(newly, nxt, stop_tok)
            stopped |= newly
            out.append(nxt)
            if stopped.all():
                out.extend([stop_tok.copy()] * (n_new - step - 1))
                break
            if step + 1 < n_new:
                logits = self.decode_step(nxt)
        self.last_stop_mask = stopped
        return np.stack(out, axis=1)

    def reuse_ratio(self) -> float:
        hits = sum(r.stats.hits for r in self.reuse)
        miss = sum(r.stats.misses for r in self.reuse)
        return hits / max(hits + miss, 1)

    def simulated_throughput(self, skip: int = 1) -> float:
        """Tokens/s under the modeled Jetson+disk pipeline (batch tokens)."""
        steps = self.step_log[skip:] or self.step_log
        if not steps:
            return 0.0
        t = sum(s.pipelined_seconds for s in steps) / len(steps)
        return self.batch / t if t > 0 else 0.0

    def overlap_report(self, skip: int = 1) -> dict:
        """Mean per-step modeled + measured overlap."""
        return summarize_steps(self.step_log[skip:] or self.step_log)

    def close(self):
        if self.prefetcher is not None:
            self.prefetcher.close()
            self.prefetcher = None
        self.store.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
