"""ShardedUpLIF — boundary-partitioned keyspace router (DESIGN.md §5).

The first concrete scaling layer of the ROADMAP's router → shards → kernels
architecture. Keys are range-partitioned into S shards at build-time
quantile boundaries, and — because a shard's entire index is a pure
``UpLIFState`` pytree — the router stores all S shards *stacked*: every
leaf carries a leading shard axis. One batched operation is then

  1. padded once on the host (exactly what the single-shard shell does),
  2. executed as ONE jitted program: the flat stacked variants of the
     pure functional ops (repro/core/fops.py §stacked) route each query
     on-device from the S-1 boundaries and run all shards via
     shard-offset index arithmetic over the [S*cap] view, so S shards
     cost a single dispatch with the same op count as one shard,
  3. returned in batch order (no re-scatter needed).

Host-side tuning actions (retrains) temporarily unstack a shard into a
regular ``UpLIF`` shell, run the existing host machinery, and restack with
re-padded common shapes. Shapes are padded to the max across shards (slot
capacity, spline knots, BMAT capacity), which is what makes the leaf-wise
stacking legal; padding obeys the fill-forward invariants so the padded
tails are inert.

State is **versioned** (DESIGN.md §8): an epoch counter orders structural
revisions and every revision records the key interval it touched, so
validation is per-interval — a split/merge only conflicts with builds
whose interval it intersects. ``snapshot(shards=...)`` freezes an
immutable view for a background build and starts a *per-interval* op-log
(several builds on disjoint intervals may be in flight at once), and
``commit(delta, replay_cap=...)`` lands a rebuilt shard with interval
validation + capped op-log replay (rebase-on-commit): when the log is
longer than ``replay_cap`` ops the commit parks in a **draining** state —
the rebuilt shells catch up batch by batch across waves while the old
rows keep serving (so reads are never stale), and the atomic reference
swap happens only when the residual log is empty. This is the substrate
of the concurrent plan/build/commit pipeline in ``repro/tuning``.
Mutations are single-writer (the serving thread), but concurrent reader
threads are safe: they grab (state, boundaries, static) as one consistent
view under the swap lock.

The public API mirrors ``UpLIF`` (lookup / insert / delete / range_query /
range_query_batch / size / memory accounting / tuning hooks), so the
serving engine and the benchmark harness can swap the router in directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import fops
from repro.core.bmat import BMAT, BPMAT, RBMAT, _make_fences, bmat_height
from repro.shapes import grow_capacity, pow2_at_least
from repro.core.state import (
    LOCATE_FUSED,
    LOCATE_STRATEGIES,
    UpLIFState,
    UpLIFStatic,
    make_halves,
    resolve_locate,
)
from repro.core.types import BMATState, GMMState, KEY_MAX, SlotsState
from repro.core.uplif import UpLIF, UpLIFConfig, bucket_width, join_pieces
from repro.kernels.ops import split_key


# --------------------------------------------------------------------------
# One jitted program drives all shards. Point ops (lookup/insert/delete/
# rank) use the *flat stacked* fops variants — shard-offset index
# arithmetic over the [S*cap] view, so the op count and per-op batch sizes
# match the single-shard program exactly (fops.py §stacked). Range scans
# unroll per shard inside one program (their cost is slice-dominated).
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("statics", "max_out"))
def _vrange(state, lo, hi, lim, *, statics, max_out):
    """Per-shard range scans, unrolled in one program. ``statics`` is a
    length-S tuple so each shard's scan runs under its OWN locate strategy
    (the per-shard dispatch axis); uniform routers pass S identical
    entries, which hash to the same jit variant as before. Variant growth
    is bounded by the distinct strategy assignments actually used — the
    controller flips a shard's strategy rarely (it is a learned action),
    and results are byte-identical across strategies regardless."""
    S = jax.tree_util.tree_leaves(state)[0].shape[0]
    outs = [
        fops.range_scan(
            jax.tree_util.tree_map(lambda x: x[s], state),
            lo[s], hi[s], lim[s], static=statics[s], max_out=max_out,
        )
        for s in range(S)
    ]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)


@functools.partial(jax.jit, static_argnames=("fanout", "pad", "with_halves"))
def _vgrow_bmat(keys, vals, *, fanout, pad, with_halves=False):
    """Grow every shard's BMAT by ``pad`` KEY_MAX slots (stacked axis 1).
    With ``with_halves`` the refreshed (hi, lo) decomposition of the grown
    keys/fences comes back too, so callers carrying a persistent
    ``state.halves`` keep it consistent without a separate device pass."""
    keys = jnp.pad(keys, ((0, 0), (0, pad)), constant_values=KEY_MAX)
    vals = jnp.pad(vals, ((0, 0), (0, pad)))
    fences = jax.vmap(lambda k: _make_fences(k, fanout))(keys)
    if with_halves:
        return keys, vals, fences, split_key(keys) + split_key(fences)
    return keys, vals, fences, None


@dataclasses.dataclass
class _ShardMeta:
    """Host-side per-shard metadata that cannot live in the stacked pytree."""

    rs_static: object
    gmm: GMMState
    alpha: float
    reservoir: np.ndarray


# --------------------------------------------------------------------------
# Versioned state: plan/build/commit support (DESIGN.md §8).
#
# ``RouterSnapshot`` freezes everything a background build needs: the stacked
# pytree (jax arrays are immutable, so holding the reference IS the freeze),
# a copy of the boundaries and of the per-shard host metadata. ``StateDelta``
# is the build's output — rebuilt shard shell(s) plus the key interval they
# own — and ``ShardedUpLIF.commit`` applies it against the LIVE router:
# interval-revision validation, capped rebase of the interval's op-log into
# the rebuilt shells, row write / restack, one atomic swap.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RouterSnapshot:
    """Immutable view of a router at one epoch; builds read ONLY this.

    ``build_id`` names the per-interval op-log ``snapshot()`` opened for
    this build; ``key_lo``/``key_hi`` bound the keyspace the build owns —
    only ops routing into that interval are logged against it, and only
    revisions intersecting it can invalidate the eventual commit."""

    epoch: int
    state: UpLIFState
    boundaries: np.ndarray
    meta: Tuple[_ShardMeta, ...]
    n_shards: int
    cfg: UpLIFConfig
    bmat_kind: str
    rs_iters: int
    build_id: int = -1
    key_lo: int = 0
    key_hi: int = int(KEY_MAX)

    def shell(self, s: int) -> UpLIF:
        """Materialize shard ``s`` of the snapshot as a host UpLIF shell.
        The shell shares the snapshot's (immutable) arrays — mutating shell
        ops build NEW arrays, so the live router is never touched."""
        return _shell_from(
            self.state, self.meta[s], self.cfg, self.bmat_kind, s
        )

    def shard_bounds(self, s: int) -> Tuple[int, int]:
        """Key interval [lo, hi) owned by shard ``s`` under this snapshot."""
        lo = int(self.boundaries[s - 1]) if s > 0 else 0
        hi = (
            int(self.boundaries[s])
            if s < self.n_shards - 1
            else int(KEY_MAX)
        )
        return lo, hi


@dataclasses.dataclass
class StateDelta:
    """Result of one background build, ready for ``commit``.

    ``kind`` is "retrain" (shells = [rebuilt shard]), "split" (shells =
    [left, right], ``boundary`` = the new cut) or "merge" (shells =
    [merged]; covers shards ``shard`` and ``shard + 1``). ``key_lo/key_hi``
    bound the keyspace the shells own — commit replays exactly the logged
    ops that route into that interval, because everything outside it still
    lives in rows the delta does not replace."""

    epoch: int
    kind: str
    shard: int
    key_lo: int
    key_hi: int
    shells: Tuple[UpLIF, ...]
    boundary: Optional[int] = None
    build_seconds: float = 0.0
    build_id: int = -1


@dataclasses.dataclass
class _BuildLog:
    """One in-flight build's rebase log: the insert/delete batches that
    routed into its key interval since the snapshot. ``pos`` is the replay
    cursor — once the build's commit is accepted, batches before ``pos``
    have already been replayed into the staged shells; the tail keeps
    growing while the commit drains."""

    build_id: int
    epoch: int                 # snapshot epoch (revision-ordinal floor)
    key_lo: int
    key_hi: int
    # entries before ``pos`` are consumed and freed (set to None)
    log: List[Optional[Tuple[str, np.ndarray, Optional[np.ndarray]]]] = (
        dataclasses.field(default_factory=list)
    )
    pos: int = 0

    @property
    def backlog_ops(self) -> int:
        return sum(len(k) for _, k, _ in self.log[self.pos:])


def intervals_overlap(lo: int, hi: int, b_lo: int, b_hi: int) -> bool:
    """Half-open [lo, hi) ∩ [b_lo, b_hi) ≠ ∅ — THE overlap predicate every
    admission/conflict path shares (snapshot, revision validation, and the
    scheduler's interval admission must agree exactly)."""
    return b_lo < hi and lo < b_hi


@dataclasses.dataclass
class _DrainingCommit:
    """An accepted commit whose replay is paced across waves.

    The rebuilt ``shells`` are STAGED: they absorb the interval's logged
    ops batch by batch (``cuts`` are the interval edges each shell owns —
    len(shells)+1 entries) while the OLD rows keep serving every read and
    write. Only when the residual log is empty do the caught-up shells
    swap in atomically — so commit cost per wave is bounded by the replay
    cap, and reads never observe a state missing acknowledged writes."""

    delta: StateDelta
    shells: Tuple[UpLIF, ...]
    cuts: Tuple[int, ...]


@dataclasses.dataclass
class MixedWave:
    """One mixed-op request wave, ready for ``ShardedUpLIF.apply_wave``.

    This is the gateway's dispatch unit (serve/gateway.py): each op kind
    carries its own batch plus an optional pre-quantized pad width
    (``pad_*``, a power of two from ``repro/shapes.padded_width``). When a
    pad width is given the router pads to exactly that width instead of
    the bulk ``bucket_width`` family — a live request stream has no
    repeating batch sizes, so only the power-of-two family keeps the jit
    cache at its warmup size. ``None`` fields / empty arrays skip that op
    kind entirely (no dispatch)."""

    lookup_keys: Optional[np.ndarray] = None
    insert_keys: Optional[np.ndarray] = None
    insert_vals: Optional[np.ndarray] = None
    delete_keys: Optional[np.ndarray] = None
    range_lo: Optional[np.ndarray] = None
    range_hi: Optional[np.ndarray] = None
    # per range, at most this many pairs (None: ``range_max_out`` each); a
    # scan is a range up to the largest key with its own limit
    range_lim: Optional[np.ndarray] = None
    pad_lookup: Optional[int] = None
    pad_insert: Optional[int] = None
    pad_delete: Optional[int] = None
    range_max_out: int = 256

    @property
    def n_ops(self) -> int:
        return sum(
            len(a)
            for a in (self.lookup_keys, self.insert_keys, self.delete_keys,
                      self.range_lo)
            if a is not None
        )


@dataclasses.dataclass
class MixedWaveResult:
    """Batch-ordered results of one ``apply_wave`` dispatch."""

    lookup_found: Optional[np.ndarray] = None
    lookup_vals: Optional[np.ndarray] = None
    delete_hit: Optional[np.ndarray] = None
    n_overflow: int = 0
    range_keys: Optional[List[np.ndarray]] = None
    range_vals: Optional[List[np.ndarray]] = None


def _shell_from(
    state: UpLIFState, meta: _ShardMeta, cfg: UpLIFConfig,
    bmat_kind: str, s: int,
) -> UpLIF:
    """Shard ``s`` of a stacked state as a regular UpLIF shell (shared,
    immutable arrays — zero copy)."""
    st: UpLIFState = jax.tree_util.tree_map(lambda x: x[s], state)
    sh = object.__new__(UpLIF)
    sh.cfg = cfg
    sh.slots = st.slots
    sh.rs_model = st.model
    sh.rs_static = meta.rs_static
    sh.gmm = meta.gmm
    sh.alpha = meta.alpha
    sh.bmat = BMAT(bmat_kind, cfg.bmat_fanout)
    sh.bmat.state = st.bmat
    sh._counters = st.counters
    sh._reservoir = meta.reservoir
    sh._rng = np.random.default_rng(s)
    sh.n_lookups = 0
    sh.n_retrains = 0
    # seed the shell's halves cache with the stacked row's slice — the
    # identity anchor makes any later array swap rebuild it automatically
    sh._halves = st.halves
    sh._halves_src = sh._halves_sources() if st.halves is not None else None
    return sh


def retrain_shell_fitted(
    shell: UpLIF, cap_now: int, gmm: Optional[GMMState] = None
):
    """Capacity-fitted full retrain of one shard shell (§7.5): the Eq. 7
    gap budget α is solved from the slot capacity the stacked state already
    has (floored at 0.05) so the rebuilt shard reuses compiled shapes —
    gaps are a tunable dial, reallocation + recompilation is a hard stall.
    Shared by the live ``retrain_shard`` fast path and the background
    build (tuning/executor.py), which must produce identical layouts."""
    n_live = int(shell.size)
    slack = max(64, shell.cfg.window) + shell.cfg.window
    # 5% safety for round-mode quantization jitter in the gap counts
    alpha_fit = (cap_now - slack) / max(n_live, 1) - 1.05
    alpha = min(shell.cfg.alpha_target, max(alpha_fit, 0.05))
    shell.retrain_full(gmm, alpha_target=alpha, gap_quantize="round")


def split_point(keys: np.ndarray) -> Optional[int]:
    """Live-key index a shard splits at, or None when the split is
    degenerate (fewer than 2 live keys, or the median equals the first key
    so the left half would be empty). The ONE definition both the live
    ``split_shard`` and the background build consult — they must agree on
    what is splittable or sync and async structure would diverge."""
    mid = len(keys) // 2
    if mid == 0 or keys[mid] == keys[0]:
        return None
    return mid


def split_shells(
    shell: UpLIF, keys: np.ndarray, vals: np.ndarray, mid: int,
    cfg: UpLIFConfig,
) -> Tuple[UpLIF, UpLIF]:
    """Two fresh shells for a shard split at live-key index ``mid``; the
    D_update reservoir partitions at the cut so both halves keep their
    observed update history."""
    cut = int(keys[mid])
    left = UpLIF(keys[:mid], vals[:mid], cfg, gmm=shell.gmm)
    right = UpLIF(keys[mid:], vals[mid:], cfg, gmm=shell.gmm)
    res = shell._reservoir
    left._reservoir = res[res < cut]
    right._reservoir = res[res >= cut]
    return left, right


def merge_shells(
    sh1: UpLIF, sh2: UpLIF, keys: np.ndarray, vals: np.ndarray,
    cfg: UpLIFConfig, rng: np.random.Generator,
) -> UpLIF:
    """One fresh shell covering two adjacent shards' live entries."""
    merged = UpLIF(keys, vals, cfg, gmm=sh1.gmm)
    res = np.concatenate([sh1._reservoir, sh2._reservoir])
    if len(res) > cfg.reservoir:
        res = rng.choice(res, cfg.reservoir, replace=False)
    merged._reservoir = res
    return merged


class ShardedUpLIF:
    """Keyspace router over S UpLIF shards stored as one stacked pytree."""

    def __init__(
        self,
        keys: np.ndarray,
        vals: Optional[np.ndarray] = None,
        config: UpLIFConfig = UpLIFConfig(),
        n_shards: int = 4,
        gmm: Optional[GMMState] = None,
    ):
        keys = np.asarray(keys, dtype=np.int64)
        order = np.argsort(keys)
        keys = keys[order]
        if vals is None:
            vals = keys.copy()
        else:
            vals = np.asarray(vals, dtype=np.int64)[order]
        uk, ui = np.unique(keys, return_index=True)
        keys, vals = uk, vals[ui]
        assert len(keys) > 0, "sharded router needs a non-empty bootstrap"

        self.n_shards = max(1, min(int(n_shards), len(keys)))
        # the delta-buffer budget is per index, not per shard
        self.cfg = dataclasses.replace(
            config,
            bmat_capacity=max(256, config.bmat_capacity // self.n_shards),
        )
        # equal-count split points; boundaries[i] = first key of shard i+1
        cuts = [
            round(i * len(keys) / self.n_shards)
            for i in range(1, self.n_shards)
        ]
        self.boundaries = (
            keys[np.asarray(cuts, dtype=np.int64)]
            if cuts
            else np.zeros(0, dtype=np.int64)
        )
        self._jbounds = jnp.asarray(self.boundaries)
        bounds = [0] + [int(c) for c in cuts] + [len(keys)]
        shells = [
            UpLIF(keys[a:b], vals[a:b], self.cfg, gmm=gmm)
            for a, b in zip(bounds[:-1], bounds[1:])
        ]
        self.bmat_kind = self.cfg.bmat_type
        self.n_lookups = 0
        self.n_retrains = 0
        self.n_splits = 0
        self.n_merges = 0
        self._rng = np.random.default_rng(0)
        # -- versioned state (plan/build/commit; DESIGN.md §8) -------------
        # epoch orders structural revisions (retrain/split/merge/switch/
        # commit-swap); every revision also records the key interval it
        # touched, so a build conflicts only with revisions that intersect
        # its own interval — disjoint builds commit independently. Each
        # in-flight build owns a per-interval op-log recording the
        # inserts/deletes that route into its keyspace, so commit can
        # rebase them onto the rebuilt shells (capped per wave: a long log
        # parks the commit in the draining map until it has caught up).
        # The lock only guards the reference swaps (and readers' reference
        # grabs): ops are still single-writer — only concurrent READERS
        # are supported against a mutating router.
        self.epoch = 0
        self.n_commits = 0
        self.n_discards = 0
        self.n_replayed_ops = 0
        self._lock = threading.RLock()
        self._logs: Dict[int, _BuildLog] = {}
        self._drains: Dict[int, _DrainingCommit] = {}
        self._revisions: List[Tuple[int, int, int]] = []  # (ordinal, lo, hi)
        self._next_build_id = 0
        # -- per-shard locate-strategy axis --------------------------------
        # every shard starts on the platform-resolved config strategy; the
        # telemetry-driven controller flips individual shards via
        # set_shard_locate. _locate_requested is what was asked for;
        # _locate_per_shard is what runs, held to the fused kernels' shape
        # guard at the current stacked shapes. _locate_value/_jcodes are the
        # cached dispatch form consumed by _static()/_read_view() (see
        # _set_locate_axis; every restack and BMAT growth refreshes them).
        self._locate_requested: List[str] = (
            [resolve_locate(self.cfg.locate)] * self.n_shards
        )
        self._locate_obs: List[Tuple[np.ndarray, float, Tuple[str, ...]]] = []
        self._restack(shells)

    # -- stacking ------------------------------------------------------------
    @staticmethod
    def _quant(n: int) -> int:
        return pow2_at_least(n)  # §7.5 shared quantization (repro/shapes.py)

    def _restack(self, shells: List[UpLIF]):
        """Pad every shard's state to common shapes and stack leaf-wise.

        Shapes are quantized to powers of two and MONOTONE across restacks
        (they grow geometrically, never shrink): a retrain / split / merge
        then almost always lands on array shapes the jit cache has already
        compiled, so background maintenance costs the host rebuild only —
        not a multi-second XLA recompile of the whole op suite. Padding is
        inert by the fill-forward invariants, so the only cost is bounded
        (< 2x) slack in the padded tails."""
        W = self.cfg.window
        # monotone vs the live stacked dims (presize/organic growth write
        # the state directly, so the state IS the source of truth)
        has_state = hasattr(self, "state")
        prev_cap = self.state.slots.keys.shape[1] if has_state else 0
        prev_bcap = self.state.bmat.keys.shape[1] if has_state else 0
        prev_knots = self.state.model.spline_keys.shape[1] if has_state else 0
        cap = max(
            self._quant(max(sh.capacity for sh in shells)), prev_cap, W
        )
        bcap = max(
            self._quant(max(sh.bmat.capacity for sh in shells)), prev_bcap
        )
        # knots arrays are tiny (K float64/int64) but their length is a jit
        # shape — when they must grow, grow with 4x headroom (floor 512) so
        # shard growth between retrains keeps hitting compiled variants;
        # when the natural need still fits the previous padding, keep it
        # (slot caps get no extra headroom: the power-of-two quant already
        # bounds slack at 2x and slots dominate memory)
        knots_need = self._quant(
            max(int(sh.rs_model.spline_keys.shape[0]) for sh in shells)
        )
        n_knots = (
            prev_knots
            if knots_need <= prev_knots
            else max(4 * knots_need, 512)
        )
        padded = [self._pad_shell(sh, cap, bcap, n_knots) for sh in shells]
        state: UpLIFState = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *padded
        )
        meta = [
            _ShardMeta(
                rs_static=sh.rs_static,
                gmm=sh.gmm,
                alpha=sh.alpha,
                reservoir=sh._reservoir,
            )
            for sh in shells
        ]
        with self._lock:
            self.state = state
            self.rs_iters = max(
                max(sh.rs_static.n_search_iters for sh in shells),
                getattr(self, "rs_iters", 0),
            )
            self._meta = meta
            self._set_locate_axis()
        assert cap % W == 0

    def _pad_shell(
        self, sh: UpLIF, cap: int, bcap: int, n_knots: int
    ) -> UpLIFState:
        """One shard's state padded to the given common stacked shapes."""
        st = sh.fstate
        d = cap - st.slots.keys.shape[0]
        slots = SlotsState(
            keys=jnp.pad(st.slots.keys, (0, d), constant_values=KEY_MAX),
            vals=jnp.pad(st.slots.vals, (0, d)),
            occ=jnp.pad(st.slots.occ, (0, d)),
        )
        k = n_knots - st.model.spline_keys.shape[0]
        model = st.model._replace(
            # repeat the last knot: interpolation degenerates to the
            # knot value, which is exactly the clamped extrapolation
            spline_keys=jnp.pad(st.model.spline_keys, (0, k), mode="edge"),
            spline_pos=jnp.pad(st.model.spline_pos, (0, k), mode="edge"),
        )
        bd = bcap - st.bmat.keys.shape[0]
        bkeys = jnp.pad(st.bmat.keys, (0, bd), constant_values=KEY_MAX)
        bmat = BMATState(
            keys=bkeys,
            vals=jnp.pad(st.bmat.vals, (0, bd)),
            fences=_make_fences(bkeys, self.cfg.bmat_fanout),
            size=st.bmat.size,
        )
        # padded arrays are NEW arrays, so the shell's cached halves (if
        # any) do not cover the pads — rebuild the row's decomposition from
        # the padded sources to keep the split-of-source invariant exact
        halves = (
            make_halves(slots, model, bmat) if st.halves is not None else None
        )
        return UpLIFState(slots=slots, model=model, bmat=bmat,
                          counters=st.counters, halves=halves)

    def _write_shard(self, s: int, sh: UpLIF) -> bool:
        """Fast path for single-shard maintenance: when the rebuilt shard
        still fits the current stacked shapes (the common case — shapes are
        quantized and monotone), write its padded row into the stacked
        pytree in place instead of restacking all S shards. Returns False
        when a dimension outgrew the stack and the caller must restack."""
        cap = int(self.state.slots.keys.shape[1])
        bcap = int(self.state.bmat.keys.shape[1])
        n_knots = int(self.state.model.spline_keys.shape[1])
        fits = (
            sh.capacity <= cap
            and sh.bmat.capacity <= bcap
            and int(sh.rs_model.spline_keys.shape[0]) <= n_knots
            and sh.rs_static.n_search_iters <= self.rs_iters
        )
        if not fits:
            return False
        row = self._pad_shell(sh, cap, bcap, n_knots)
        state = jax.tree_util.tree_map(
            lambda st, r: st.at[s].set(r), self.state, row
        )
        with self._lock:
            self.state = state
            self._meta[s] = _ShardMeta(
                rs_static=sh.rs_static,
                gmm=sh.gmm,
                alpha=sh.alpha,
                reservoir=sh._reservoir,
            )
        return True

    def _unstack_shell(self, s: int) -> UpLIF:
        """Materialize shard ``s`` as a regular UpLIF shell (shared arrays)."""
        return _shell_from(
            self.state, self._meta[s], self.cfg, self.bmat_kind, s
        )

    # -- per-shard locate dispatch ---------------------------------------------
    def _set_locate_axis(self):
        """Resolve ``_locate_requested`` against the fused kernels' shape
        guard at the current stacked shapes into ``_locate_per_shard`` (the
        strategies that execute: a shard asking for fused where the guard
        refuses runs — and reports — spline), then refresh its cached
        dispatch form.

        ``_locate_value`` is what ``_static().locate`` carries: the single
        strategy string when the assignment is uniform (the common case —
        identical jit variants to a strategy-less router), else the SORTED
        tuple of distinct strategies in play, so the static universe stays
        inside the ≤7-value family regardless of which shard runs what.
        ``_jcodes`` is the traced companion: per-shard int32 indices into
        that tuple (None when uniform). Callers mutate ``_locate_requested``
        or the state's shapes under the lock and call this before releasing
        it."""
        fits = self._fused_fits()
        self._locate_per_shard = [
            resolve_locate(r, fits) for r in self._locate_requested
        ]
        distinct = sorted(set(self._locate_per_shard))
        if len(distinct) == 1:
            self._locate_value = distinct[0]
            self._jcodes = None
        else:
            self._locate_value = tuple(distinct)
            pos = {strat: i for i, strat in enumerate(distinct)}
            self._jcodes = jnp.asarray(
                np.asarray(
                    [pos[s] for s in self._locate_per_shard], dtype=np.int32
                )
            )

    def set_shard_locate(self, s: int, strategy: str) -> bool:
        """Pin shard ``s``'s locate strategy (the controller's
        switch-locate action). Metadata-only: no state arrays move and the
        strategy never changes what a query returns (the three strategies
        are byte-identical by the equivalence contract), so — unlike
        ``switch_bmat_type`` — this records NO revision and needs no
        in-flight-build veto. Returns True when the strategy that executes
        changed (fused where the shape guard refuses still runs spline)."""
        assert 0 <= s < self.n_shards
        strategy = resolve_locate(strategy)
        with self._lock:
            before = self._locate_per_shard[s]
            self._locate_requested[s] = strategy
            self._set_locate_axis()
            return self._locate_per_shard[s] != before

    def _fused_fits(self) -> bool:
        st = self.state
        return fops.fused_fits(st.slots.keys, st.model, st.bmat)

    def shard_locate(self) -> Tuple[str, ...]:
        """Per-shard strategies that execute (telemetry snapshot input)."""
        with self._lock:
            return tuple(self._locate_per_shard)

    def locate_options(self) -> Tuple[str, ...]:
        """Strategies a shard can run at the current stacked shapes."""
        if self._fused_fits():
            return LOCATE_STRATEGIES
        return tuple(x for x in LOCATE_STRATEGIES if x != LOCATE_FUSED)

    def drain_locate_obs(
        self,
    ) -> List[Tuple[np.ndarray, float, Tuple[str, ...]]]:
        """Hand the accumulated (per-shard query counts, wall seconds,
        strategy assignment) lookup observations to the telemetry layer
        and reset the buffer."""
        with self._lock:
            obs, self._locate_obs = self._locate_obs, []
        return obs

    def _static(self) -> UpLIFStatic:
        # cfg.locate is resolved per shard at init/set_shard_locate time
        # ("auto" -> spline) and held to the fused
        # guard at every shape change (_set_locate_axis)
        return UpLIFStatic(
            window=self.cfg.window,
            movement_k=self.cfg.movement_k,
            rs_iters=self.rs_iters,
            insert_rounds=self.cfg.insert_rounds,
            fanout=self.cfg.bmat_fanout,
            bmat_kind=self.bmat_kind,
            locate=self._locate_value,
        )

    def _read_view(self):
        """One consistent (state, boundaries, jbounds, codes, static) view.

        Readers on other threads race the commit swap only at reference
        granularity: grabbing all five under the swap lock guarantees the
        static/boundary/strategy metadata matches the pytree generation, so
        a lookup issued mid-commit runs entirely against either the old or
        the new state — never a mix (the torn-read stress test pins this)."""
        with self._lock:
            return (
                self.state, self.boundaries, self._jbounds, self._jcodes,
                self._static(),
            )

    # -- routing ---------------------------------------------------------------
    def _route(self, keys: np.ndarray) -> np.ndarray:
        """Shard id per key: shard s owns [boundaries[s-1], boundaries[s])."""
        return np.searchsorted(self.boundaries, keys, side="right")

    def _bucket(self, n: int) -> int:
        return bucket_width(n, self.cfg.batch_bucket)

    def _observe_updates(self, keys: np.ndarray):
        """Feed each shard's D_update reservoir (Phase 2) so router retrains
        refresh the GMM exactly like single-shard UpLIF does."""
        cap = self.cfg.reservoir
        take = (
            keys
            if len(keys) <= cap
            else self._rng.choice(keys, cap, replace=False)
        )
        sid = self._route(take)
        for s in range(self.n_shards):
            sub = take[sid == s]
            if len(sub) == 0:
                continue
            m = self._meta[s]
            res = np.concatenate([m.reservoir, sub])
            if len(res) > cap:
                res = self._rng.choice(res, cap, replace=False)
            m.reservoir = res

    def _pad_route(self, keys: np.ndarray, *aux, width: Optional[int] = None):
        """Pad the batch to a bucketed width — ONE batch for all shards;
        the stacked ops route per query on-device from the boundaries, so
        the host does exactly what the single-shard shell does. ``width``
        overrides the bucket (the gateway passes its power-of-two flush
        width so live-stream dispatches reuse the warmup jit variants)."""
        n = len(keys)
        B = self._bucket(max(n, 1)) if width is None else int(width)
        assert B >= n, f"pad width {B} below batch size {n}"
        q = np.full(B, KEY_MAX, dtype=np.int64)
        q[:n] = keys
        outs = []
        for a in aux:
            m = np.zeros(B, dtype=np.int64)
            m[:n] = a
            outs.append(jnp.asarray(m))
        return jnp.asarray(q), n, *outs

    # -- queries ---------------------------------------------------------------
    @obs.traced("router.lookup")
    def lookup(
        self, queries: np.ndarray, pad_to: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        with obs.span("router.prepare"):
            queries = np.asarray(queries, dtype=np.int64)
            q, n = self._pad_route(queries, width=pad_to)
        with obs.span("router.view"):
            state, boundaries, jb, codes, static = self._read_view()
        t0 = time.perf_counter()
        with obs.span("router.launch"):
            f, v = fops.slookup(state, q, jb, codes, static=static)
        with obs.span("router.wait"):  # sync: time the whole dispatch
            f = obs.fetch("router.lookup", f)
            v = obs.fetch("router.lookup", v)
        dt = time.perf_counter() - t0
        self.n_lookups += n
        if n:
            # per-shard latency attribution for the locate-strategy
            # controller: one searchsorted + bincount per dispatch is the
            # whole host cost of the telemetry feed
            counts = np.bincount(
                np.searchsorted(boundaries, queries[:n], side="right"),
                minlength=len(boundaries) + 1,
            )
            with self._lock:
                if len(self._locate_obs) < 1024:  # bounded between drains
                    self._locate_obs.append(
                        (counts, dt, tuple(self._locate_per_shard))
                    )
        return f[:n], v[:n]

    def _log_op(
        self, kind: str, keys: np.ndarray, vals: Optional[np.ndarray]
    ):
        """Record one op batch against every in-flight build whose key
        interval it intersects (a build only ever rebases ops it owns)."""
        for bl in self._logs.values():
            m = (keys >= bl.key_lo) & (keys < bl.key_hi)
            if not m.any():
                continue
            # mask indexing already yields fresh arrays — no extra copy
            bl.log.append(
                (kind, keys[m], vals[m] if vals is not None else None)
            )

    @obs.traced("router.insert")
    def insert(
        self,
        keys: np.ndarray,
        vals: Optional[np.ndarray] = None,
        pad_to: Optional[int] = None,
    ) -> int:
        """Upsert a batch; returns how many keys went to the BMAT. Counts
        the calls (``sinsert.calls``), the batch's keys (``sinsert.keys``),
        those still pending when the accept rounds start
        (``sinsert.round_keys``), the accept rounds run
        (``sinsert.rounds``) and the keys that went to the BMAT
        (``sinsert.bmat_keys``)."""
        with obs.span("router.prepare"):
            keys = np.asarray(keys, dtype=np.int64)
            if vals is None:
                vals = keys.copy()
            vals = np.asarray(vals, dtype=np.int64)
            if len(keys) == 0:
                return 0
            if self._logs:
                self._log_op("insert", keys, vals)
            self._observe_updates(keys)
            q, n, vm = self._pad_route(keys, vals, width=pad_to)
        with obs.span("router.capacity"):
            self._ensure_bmat_capacity(int(q.shape[0]))
        with obs.span("router.launch"):
            state, res = fops.sinsert(
                self.state, q, vm, self._jbounds, self._jcodes,
                static=self._static(),
            )
        with self._lock:
            self.state = state
        with obs.span("router.wait"):
            n_over, n_round, n_rounds = obs.fetch(
                "router.insert",
                (res.n_overflow, res.n_round_keys, res.n_rounds),
            )
        obs.count("sinsert.calls")
        obs.count("sinsert.keys", n)
        obs.count("sinsert.round_keys", int(n_round))
        obs.count("sinsert.rounds", int(n_rounds))
        obs.count("sinsert.bmat_keys", int(n_over))
        return int(n_over)

    @obs.traced("router.delete")
    def delete(
        self, keys: np.ndarray, pad_to: Optional[int] = None
    ) -> np.ndarray:
        with obs.span("router.prepare"):
            keys = np.asarray(keys, dtype=np.int64)
            if self._logs:
                self._log_op("delete", keys, None)
            q, n = self._pad_route(keys, width=pad_to)
        with obs.span("router.launch"):
            state, hit = fops.sdelete(
                self.state, q, self._jbounds, self._jcodes,
                static=self._static(),
            )
        with self._lock:
            self.state = state
        with obs.span("router.wait"):
            return obs.fetch("router.delete", hit)[:n]

    def range_query(self, lo: int, hi: int, max_out: int = 1024):
        ks, vs = self.range_query_batch(
            np.asarray([lo], dtype=np.int64),
            np.asarray([hi], dtype=np.int64),
            max_out,
        )
        return ks[0], vs[0]

    @obs.traced("router.range")
    def range_query_batch(
        self, lo: np.ndarray, hi: np.ndarray, max_out: int = 1024,
        lim: Optional[np.ndarray] = None,
    ):
        """Per query, the first ``lim`` live (key, value) pairs (``max_out``
        where ``lim`` is None; never more) with lo <= key <= hi, in key
        order. A query starts in the shard that owns ``lo``, and every
        shard's queries run in ONE ``_vrange`` program. A query that pass
        could not finish runs again in a further pass of the same program:
        from its slice's front in the same shard where tombstones or gaps
        thinned the slice (a refill), or from the next shard's lower edge
        where its shard ran out below ``hi`` (a spill). The pieces
        concatenate in pass order, which is key order because the
        partition is a range partition."""
        lo = np.asarray(lo, dtype=np.int64)
        hi = np.asarray(hi, dtype=np.int64)
        n = len(lo)
        left = np.full(n, max_out, dtype=np.int64)
        if lim is not None:
            left = np.minimum(np.asarray(lim, dtype=np.int64), max_out)
        with obs.span("router.view"), self._lock:
            state, boundaries = self.state, self.boundaries
            static = self._static()
            per_shard = tuple(self._locate_per_shard)
        # range scans unroll per shard, so mixed dispatch is just each
        # shard's scan compiled under its own (uniform) strategy
        statics = tuple(static._replace(locate=loc) for loc in per_shard)
        obs.count("range.queries", n)
        upper = np.append(boundaries, KEY_MAX)  # shard s owns keys < upper[s]
        parts_k: List[List[np.ndarray]] = [[] for _ in range(n)]
        parts_v: List[List[np.ndarray]] = [[] for _ in range(n)]
        qi, sid, cur = np.arange(n), np.searchsorted(boundaries, lo, "right"), lo
        with contextlib.ExitStack() as passes:
            rescan = None
            while len(qi):
                ks, vs, cn, covered, front = self._range_pass(
                    state, statics, sid, cur, hi[qi], left[qi], max_out
                )
                with obs.span("router.assemble"):
                    for i, q in enumerate(qi):
                        if cn[i]:
                            parts_k[q].append(ks[i, : cn[i]])
                            parts_v[q].append(vs[i, : cn[i]])
                    left[qi] -= cn
                    refill = ~covered
                    spill = (covered & (left[qi] > 0) & (sid < len(upper) - 1)
                             & (hi[qi] >= upper[sid]))
                    if np.any(front[refill] <= cur[refill]):
                        raise RuntimeError("range scan made no progress")
                    obs.count("range.refills", int(refill.sum()))
                    obs.count("range.spills", int(spill.sum()))
                    nxt = refill | spill
                    cur = np.where(refill, front, upper[sid])[nxt]
                    sid = (sid + spill)[nxt]
                    qi = qi[nxt]
                if len(qi) and rescan is None:  # the follow-up passes
                    rescan = passes.enter_context(obs.span("router.rescan"))
        with obs.span("router.assemble"):
            out_k, out_v = join_pieces(parts_k), join_pieces(parts_v)
            obs.count("range.keys", sum(len(k) for k in out_k))
        return out_k, out_v

    def _range_pass(self, state, statics, sid, lo, hi, lim, max_out):
        """One run of ``_vrange``: query i in a row of shard ``sid[i]``.
        Returns, per query, its pairs (``[n, max_out]``), count, whether
        its answer is whole and its slice's front."""
        with obs.span("router.prepare"):
            S = len(statics)
            counts = np.bincount(sid, minlength=S)
            order = np.argsort(sid, kind="stable")
            rows = np.empty(len(sid), dtype=np.int64)
            rows[order] = np.arange(len(sid)) - np.repeat(
                np.cumsum(counts) - counts, counts
            )
            B = self._bucket(max(int(counts.max()), 1))
            lo_m = np.full((S, B), KEY_MAX, dtype=np.int64)
            hi_m = np.zeros((S, B), dtype=np.int64)
            lim_m = np.zeros((S, B), dtype=np.int64)
            lo_m[sid, rows], hi_m[sid, rows], lim_m[sid, rows] = lo, hi, lim
            obs.count("range.rows", len(sid))
            obs.count("range.rows_run", S * B)
        with obs.span("router.launch"):
            res = _vrange(
                state, jnp.asarray(lo_m), jnp.asarray(hi_m),
                jnp.asarray(lim_m), statics=statics, max_out=max_out,
            )
        with obs.span("router.wait"):
            res = obs.fetch("router.range", res)
        return tuple(np.asarray(x)[sid, rows] for x in res)

    @obs.traced("router.apply_wave")
    def apply_wave(self, wave: MixedWave) -> MixedWaveResult:
        """Dispatch one mixed-op wave (the gateway's flush unit).

        Op kinds execute in the canonical wave order **inserts → deletes →
        lookups → ranges**: writes land before reads, so a client whose
        write future resolved in ANY earlier wave — and one whose write
        rides in this very wave — observes it (read-your-writes through
        the gateway; pinned by tests/test_gateway.py). Each op kind is one
        jitted dispatch at its ``pad_*`` width; empty kinds cost nothing."""
        res = MixedWaveResult()
        if wave.insert_keys is not None and len(wave.insert_keys):
            res.n_overflow = self.insert(
                wave.insert_keys, wave.insert_vals, pad_to=wave.pad_insert
            )
        if wave.delete_keys is not None and len(wave.delete_keys):
            res.delete_hit = self.delete(
                wave.delete_keys, pad_to=wave.pad_delete
            )
        if wave.lookup_keys is not None and len(wave.lookup_keys):
            res.lookup_found, res.lookup_vals = self.lookup(
                wave.lookup_keys, pad_to=wave.pad_lookup
            )
        if wave.range_lo is not None and len(wave.range_lo):
            res.range_keys, res.range_vals = self.range_query_batch(
                wave.range_lo, wave.range_hi, max_out=wave.range_max_out,
                lim=wave.range_lim,
            )
        return res

    def adjusted_predict(self, queries: np.ndarray) -> np.ndarray:
        """Global logical rank = shard-local rank + total live keys in the
        shards left of the owning shard."""
        queries = np.asarray(queries, dtype=np.int64)
        state, boundaries, jb, codes, static = self._read_view()
        # a preceding shard contributes its live in-place keys plus its FULL
        # BMAT entry count — the bias r(k) counts tombstones too, matching
        # the single-shard BMAT rank semantics
        sizes = np.asarray(state.counters.n_keys) + np.asarray(
            state.bmat.size, dtype=np.int64
        )
        base = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        q, n = self._pad_route(queries)
        rank = np.asarray(fops.srank(state, q, jb, codes, static=static))
        sid = np.searchsorted(boundaries, queries, side="right")
        return rank[:n] + base[sid]

    # -- capacity management ---------------------------------------------------
    def _ensure_bmat_capacity(self, incoming: int):
        sizes = obs.fetch("router.capacity", self.state.bmat.size)
        bcap = int(self.state.bmat.keys.shape[1])
        need = int(sizes.max()) + incoming
        if need <= bcap - 1:
            return
        new_cap = grow_capacity(need)
        keys, vals, fences, bh = _vgrow_bmat(
            self.state.bmat.keys,
            self.state.bmat.vals,
            fanout=self.cfg.bmat_fanout,
            pad=new_cap - bcap,
            with_halves=self.state.halves is not None,
        )
        with self._lock:
            halves = self.state.halves
            if bh is not None:
                halves = halves._replace(
                    bmat_hi=bh[0], bmat_lo=bh[1],
                    fence_hi=bh[2], fence_lo=bh[3],
                )
            self.state = self.state._replace(
                bmat=BMATState(
                    keys=keys, vals=vals, fences=fences,
                    size=self.state.bmat.size,
                ),
                halves=halves,
            )
            self._set_locate_axis()

    # -- versioned-state protocol (plan/build/commit; DESIGN.md §8) ------------
    @property
    def _tracking(self) -> bool:
        """True while any build's op-log is active (back-compat probe)."""
        return bool(self._logs)

    def _shard_interval(self, s_first: int, s_last: int = -1) -> Tuple[int, int]:
        """Key interval [lo, hi) owned by the contiguous shard run
        ``s_first .. s_last`` under the CURRENT boundaries."""
        if s_last < 0:
            s_last = s_first
        lo = 0 if s_first == 0 else int(self.boundaries[s_first - 1])
        hi = (
            int(KEY_MAX)
            if s_last >= self.n_shards - 1
            else int(self.boundaries[s_last])
        )
        return lo, hi

    def _record_revision(self, lo: int, hi: int):
        """Mark a structural revision over [lo, hi): builds whose interval
        intersects it can no longer commit (their shard indexing and row
        contents are stale); disjoint builds are untouched."""
        self._revisions.append((self.epoch, int(lo), int(hi)))
        self.epoch += 1
        self._prune_revisions()

    def _prune_revisions(self):
        """Drop revisions no active build could still conflict with."""
        if not self._logs:
            self._revisions.clear()
            return
        floor = min(bl.epoch for bl in self._logs.values())
        self._revisions = [r for r in self._revisions if r[0] >= floor]

    def _conflicts(self, epoch: int, lo: int, hi: int) -> bool:
        return any(
            e >= epoch and intervals_overlap(lo, hi, r_lo, r_hi)
            for e, r_lo, r_hi in self._revisions
        )

    def active_intervals(self) -> List[Tuple[int, int]]:
        """Key intervals owned by in-flight builds and draining commits —
        the scheduler's admission-control input (new plans must not
        overlap any of these)."""
        return [(bl.key_lo, bl.key_hi) for bl in self._logs.values()]

    def snapshot(
        self, shards: Optional[Sequence[int]] = None
    ) -> RouterSnapshot:
        """Freeze the current state for a background build of the given
        contiguous shard run (default: the whole router) and open its
        per-interval op-log. Builds on disjoint intervals may be in flight
        concurrently; an overlapping snapshot is a caller bug — the
        scheduler admission-controls by interval overlap."""
        if shards is None:
            shards = range(self.n_shards)
        shards = sorted(int(s) for s in shards)
        if not shards or shards[0] < 0 or shards[-1] >= self.n_shards:
            raise ValueError(f"shards out of range: {shards}")
        if shards != list(range(shards[0], shards[-1] + 1)):
            # a gap would open a log over keyspace the build never rebuilds
            raise ValueError(f"shards must be contiguous: {shards}")
        lo, hi = self._shard_interval(shards[0], shards[-1])
        for b_lo, b_hi in self.active_intervals():
            if intervals_overlap(lo, hi, b_lo, b_hi):
                raise RuntimeError(
                    "a build is already in flight for an overlapping key "
                    f"interval [{b_lo}, {b_hi})"
                )
        with self._lock:
            self._next_build_id += 1
            bid = self._next_build_id
            self._logs[bid] = _BuildLog(
                build_id=bid, epoch=self.epoch, key_lo=lo, key_hi=hi
            )
            return RouterSnapshot(
                epoch=self.epoch,
                state=self.state,
                boundaries=self.boundaries.copy(),
                meta=tuple(dataclasses.replace(m) for m in self._meta),
                n_shards=self.n_shards,
                cfg=self.cfg,
                bmat_kind=self.bmat_kind,
                rs_iters=self.rs_iters,
                build_id=bid,
                key_lo=lo,
                key_hi=hi,
            )

    def discard_build(self, build_id: Optional[int] = None):
        """Drop a build's op-log and any staged drain (build failed, was
        abandoned, or its interval was revised under it). ``None`` discards
        every active build (shutdown path)."""
        ids = list(self._logs) if build_id is None else [build_id]
        for bid in ids:
            if self._logs.pop(bid, None) is not None:
                self.n_discards += 1
            self._drains.pop(bid, None)
        self._prune_revisions()

    def _resolve_shard(self, delta: StateDelta) -> Optional[int]:
        """Map the delta's key interval back to a CURRENT shard index.
        Disjoint commits during the build/drain only shift indices; the
        interval itself must still be exactly one shard (retrain/split) or
        one adjacent pair (merge) — anything else is a conflict the
        revision check should already have caught."""
        s = int(np.searchsorted(self.boundaries, delta.key_lo, side="right"))
        if s >= self.n_shards:
            return None
        lo, hi = self._shard_interval(s)
        if lo != delta.key_lo:
            return None
        if delta.kind == "merge":
            if s + 1 >= self.n_shards:
                return None
            hi = self._shard_interval(s + 1)[1]
        return s if hi == delta.key_hi else None

    def commit(
        self, delta: StateDelta, replay_cap: Optional[int] = None
    ) -> bool:
        """Accept a finished build. Validates the interval first: any
        structural revision since the snapshot that intersects the delta's
        keyspace (an overlapping commit, a direct retrain/split/merge, a
        BMAT-type switch) invalidates it — the build is discarded and the
        caller replans. Disjoint revisions do NOT conflict: the delta's
        shard index is re-resolved from its key interval.

        On acceptance the interval's logged ops are replayed into the
        rebuilt shells (rebase-on-commit), whole batches at a time, until
        ``replay_cap`` ops have been replayed (None = unbounded). If the
        log runs dry the caught-up shells swap in atomically and the
        commit completes now; otherwise it parks in the draining state —
        the OLD rows keep serving reads and writes (new ops into the
        interval keep appending to the log), and ``advance_drain`` resumes
        the replay at later wave boundaries. Returns False on conflict,
        True when the commit was accepted (committed or draining)."""
        bl = self._logs.get(delta.build_id)
        if bl is None or self._conflicts(delta.epoch, delta.key_lo,
                                         delta.key_hi):
            self.discard_build(delta.build_id)
            return False
        if self._resolve_shard(delta) is None:
            self.discard_build(delta.build_id)
            return False
        if delta.kind == "split":
            cuts = (delta.key_lo, int(delta.boundary), delta.key_hi)
        else:
            cuts = (delta.key_lo, delta.key_hi)
        drain = _DrainingCommit(delta=delta, shells=delta.shells, cuts=cuts)
        self._drains[delta.build_id] = drain
        self._advance_one(drain, replay_cap)
        return True

    @property
    def draining(self) -> bool:
        return bool(self._drains)

    def draining_builds(self) -> List[int]:
        return list(self._drains)

    def drain_backlog(self, build_id: Optional[int] = None) -> int:
        """Un-replayed ops still owed by draining commits."""
        ids = self.draining_builds() if build_id is None else [build_id]
        return sum(
            self._logs[b].backlog_ops for b in ids if b in self._logs
        )

    def advance_drain(
        self, build_id: int, replay_cap: Optional[int] = None
    ) -> bool:
        """Replay up to ``replay_cap`` more ops of one draining commit
        (whole batches, so pacing never changes the replayed call
        sequence); swap atomically if it caught up. Aborts the drain when
        an intersecting revision landed since the snapshot. Returns True
        when the commit completed (swapped) this call."""
        drain = self._drains.get(build_id)
        if drain is None:
            return False
        bl = self._logs[build_id]
        if self._conflicts(bl.epoch, bl.key_lo, bl.key_hi):
            self.discard_build(build_id)
            return False
        return self._advance_one(drain, replay_cap)

    def advance_drains(self, replay_cap: Optional[int] = None) -> int:
        """Wave-boundary hook: advance every draining commit; returns the
        number that completed (swapped) this call."""
        return sum(
            self.advance_drain(bid, replay_cap)
            for bid in self.draining_builds()
        )

    def _advance_one(
        self, drain: _DrainingCommit, replay_cap: Optional[int]
    ) -> bool:
        """Replay whole logged batches into the staged shells until the
        op budget is spent or the log is dry; swap when dry. Runs on the
        serving thread, so no new ops can interleave mid-call — "dry after
        the last batch" really is the catch-up point."""
        bl = self._logs[drain.delta.build_id]
        done = 0
        while bl.pos < len(bl.log):
            if replay_cap is not None and done >= replay_cap:
                return False
            kind, keys, vals = bl.log[bl.pos]
            bl.log[bl.pos] = None  # consumed: free it — a long drain must
            bl.pos += 1            # hold only the unreplayed tail
            for shell, c_lo, c_hi in zip(
                drain.shells, drain.cuts[:-1], drain.cuts[1:]
            ):
                m = (keys >= c_lo) & (keys < c_hi)
                if not m.any():
                    continue
                if kind == "insert":
                    shell.insert(keys[m], vals[m])
                else:
                    shell.delete(keys[m])
            done += len(keys)
            self.n_replayed_ops += len(keys)
        return self._finish_drain(drain)

    def _finish_drain(self, drain: _DrainingCommit) -> bool:
        """The wave-boundary atomic swap: land the caught-up shells. The
        shells now hold exactly the old rows' live contents (snapshot +
        every logged op, in arrival order) in the rebuilt layout, so the
        swap changes layout — never what a lookup returns."""
        delta = drain.delta
        s = self._resolve_shard(delta)
        if s is None:  # a disjoint revision SHOULD leave us resolvable;
            # anything else means the interval was revised under us
            self.discard_build(delta.build_id)
            return False
        del self._drains[delta.build_id]
        del self._logs[delta.build_id]
        with self._lock:
            self._apply_delta(delta, s, drain.shells)
            self._record_revision(delta.key_lo, delta.key_hi)
            self.n_commits += 1
        return True

    def _apply_delta(
        self, delta: StateDelta, s: int, shells: Tuple[UpLIF, ...]
    ):
        if delta.kind == "retrain":
            sh = shells[0]
            if not self._write_shard(s, sh):
                self._restack(
                    [
                        sh if i == s else self._unstack_shell(i)
                        for i in range(self.n_shards)
                    ]
                )
            self.n_retrains += 1
        elif delta.kind == "split":
            live = [self._unstack_shell(i) for i in range(self.n_shards)]
            with self._lock:
                self.boundaries = np.insert(
                    self.boundaries, s, delta.boundary
                )
                self._jbounds = jnp.asarray(self.boundaries)
                self.n_shards += 1
                self.n_splits += 1
                # both halves inherit the split shard's locate strategy
                self._locate_requested.insert(s, self._locate_requested[s])
                self._restack(live[:s] + list(shells) + live[s + 1:])
        elif delta.kind == "merge":
            live = [self._unstack_shell(i) for i in range(self.n_shards)]
            with self._lock:
                self.boundaries = np.delete(self.boundaries, s)
                self._jbounds = jnp.asarray(self.boundaries)
                self.n_shards -= 1
                self.n_merges += 1
                # the merged shard keeps the left member's strategy
                del self._locate_requested[s + 1]
                self._restack(live[:s] + list(shells) + live[s + 2:])
        else:
            raise ValueError(f"unknown delta kind: {delta.kind}")

    # -- tuning hooks (Section 4.2, applied per shard) -------------------------
    def retrain_full(self, gmm: Optional[GMMState] = None):
        shells = [self._unstack_shell(s) for s in range(self.n_shards)]
        for sh in shells:
            sh.retrain_full(gmm)
        self._restack(shells)
        self.n_retrains += 1
        self._record_revision(0, int(KEY_MAX))

    def retrain_shard(self, s: int, gmm: Optional[GMMState] = None):
        """Targeted tuning action: full retrain of ONE shard — absorb its
        delta buffer, drop its tombstones, re-nullify with ``gmm`` (the
        tuning subsystem's D_update forecast) or the shard reservoir refit.
        Orders of magnitude cheaper than ``retrain_full`` when only one
        shard's buffer is hot, which is the common case under skew: the
        rebuilt shard usually still fits the stacked shapes, so the update
        is one padded row write — no restack, no new jit variants. The Eq. 7
        gap budget α is fitted to the capacity the stacked state already
        has (floored at 0.05): gaps are a tunable dial, reallocation +
        recompilation is a hard stall, so the retrain trades the former for
        the latter. When the shard outgrows even a low-α layout the arrays
        genuinely grow — that is the regime where the controller's
        split-shard action pays instead."""
        assert 0 <= s < self.n_shards
        shell = self._unstack_shell(s)
        retrain_shell_fitted(
            shell, int(self.state.slots.keys.shape[1]), gmm=gmm
        )
        if not self._write_shard(s, shell):
            shells = [
                shell if i == s else self._unstack_shell(i)
                for i in range(self.n_shards)
            ]
            self._restack(shells)
        self.n_retrains += 1
        self._record_revision(*self._shard_interval(s))

    def retrain_subset(self, quantiles: int = 16) -> int:
        # absorb on the shard with the largest delta buffer (cheapest win)
        sizes = np.asarray(self.state.bmat.size)
        worst = int(np.argmax(sizes))
        shells = [self._unstack_shell(s) for s in range(self.n_shards)]
        absorbed = shells[worst].retrain_subset(quantiles)
        self._restack(shells)
        self.n_retrains += 1
        self._record_revision(*self._shard_interval(worst))
        return absorbed

    def switch_bmat_type(self):
        # the BMAT layout is shared by every shard, so the switch revises
        # the WHOLE keyspace: any in-flight build's shells were built for
        # the other traversal and must be discarded at their commit
        with self._lock:
            self.bmat_kind = BPMAT if self.bmat_kind == RBMAT else RBMAT
            self._record_revision(0, int(KEY_MAX))

    # -- structural maintenance (tuning-subsystem entry points) ----------------
    def split_shard(self, s: int) -> bool:
        """Split shard ``s`` at its median live key into two shards.

        The keyspace partition stays a range partition (one new boundary at
        the median key), so routing, range-query shard order and the global
        rank arithmetic all keep working unchanged. Returns False when the
        shard is too small to split (fewer than 2 live keys)."""
        assert 0 <= s < self.n_shards
        shells = [self._unstack_shell(i) for i in range(self.n_shards)]
        keys, vals = shells[s].extract_live()
        mid = split_point(keys)
        if mid is None:
            return False
        cut = int(keys[mid])  # first key of the right half == new boundary
        left, right = split_shells(shells[s], keys, vals, mid, self.cfg)
        lo, hi = self._shard_interval(s)
        with self._lock:
            self.boundaries = np.insert(self.boundaries, s, cut)
            self._jbounds = jnp.asarray(self.boundaries)
            self.n_shards += 1
            self.n_splits += 1
            self._locate_requested.insert(s, self._locate_requested[s])
            self._restack(shells[:s] + [left, right] + shells[s + 1:])
            self._record_revision(lo, hi)
        return True

    def merge_shards(self, s: int) -> bool:
        """Merge shard ``s`` with its right neighbor ``s + 1`` (adjacent
        shards own adjacent key ranges, so a concat preserves sortedness).
        Returns False when there is no right neighbor or the merged shard
        would be empty."""
        if self.n_shards < 2 or not (0 <= s < self.n_shards - 1):
            return False
        shells = [self._unstack_shell(i) for i in range(self.n_shards)]
        k1, v1 = shells[s].extract_live()
        k2, v2 = shells[s + 1].extract_live()
        keys = np.concatenate([k1, k2])
        vals = np.concatenate([v1, v2])
        if len(keys) == 0:
            return False
        merged = merge_shells(shells[s], shells[s + 1], keys, vals,
                              self.cfg, self._rng)
        lo = self._shard_interval(s)[0]
        hi = self._shard_interval(s + 1)[1]
        with self._lock:
            self.boundaries = np.delete(self.boundaries, s)
            self._jbounds = jnp.asarray(self.boundaries)
            self.n_shards -= 1
            self.n_merges += 1
            del self._locate_requested[s + 1]
            self._restack(shells[:s] + [merged] + shells[s + 2:])
            self._record_revision(lo, hi)
        return True

    def presize_bmat(self, per_shard_capacity: int) -> bool:
        """Proactive delta-buffer growth (forecast-driven): raise every
        shard's BMAT capacity to at least ``per_shard_capacity`` NOW, so a
        predicted insert burst neither reallocates nor recompiles on the
        hot path. Growth only — capacities never shrink mid-run."""
        bcap = int(self.state.bmat.keys.shape[1])
        need = int(per_shard_capacity)
        if need <= bcap:
            return False
        new_cap = pow2_at_least(need)
        keys, vals, fences, bh = _vgrow_bmat(
            self.state.bmat.keys,
            self.state.bmat.vals,
            fanout=self.cfg.bmat_fanout,
            pad=new_cap - bcap,
            with_halves=self.state.halves is not None,
        )
        with self._lock:
            halves = self.state.halves
            if bh is not None:
                halves = halves._replace(
                    bmat_hi=bh[0], bmat_lo=bh[1],
                    fence_hi=bh[2], fence_lo=bh[3],
                )
            self.state = self.state._replace(
                bmat=BMATState(
                    keys=keys, vals=vals, fences=fences,
                    size=self.state.bmat.size,
                ),
                halves=halves,
            )
            self._set_locate_axis()
        return True

    # -- accounting ------------------------------------------------------------
    @property
    def size(self) -> int:
        c = self.state.counters
        return int(jnp.sum(c.n_keys + c.n_bmat_live))

    @property
    def n_keys(self) -> int:
        return int(jnp.sum(self.state.counters.n_keys))

    @property
    def capacity(self) -> int:
        return int(np.prod(self.state.slots.keys.shape))

    def memory_bytes(self, modeled: bool = False) -> int:
        from repro.core.gmm import gmm_memory_bytes

        arrays = (
            list(self.state.slots) + list(self.state.model)
            + list(self.state.bmat)
        )
        total = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)
        return total + sum(gmm_memory_bytes(m.gmm) for m in self._meta)

    def index_bytes(self, modeled: bool = False) -> int:
        from repro.core.gmm import gmm_memory_bytes

        arrays = list(self.state.model) + list(self.state.bmat)
        total = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays)
        return total + sum(gmm_memory_bytes(m.gmm) for m in self._meta)

    def measures(self) -> dict:
        """Aggregate Section 4.1 measures (worst-case heights, summed sizes)."""
        c = self.state.counters
        bsizes = np.asarray(self.state.bmat.size)
        heights = [
            bmat_height(int(b), self.bmat_kind, self.cfg.bmat_fanout)
            for b in bsizes
        ]
        return {
            "bmat_height": max(heights),
            "granularity": int(np.min(np.asarray(c.min_granularity))),
            "error_scaling": float(np.mean([m.alpha for m in self._meta])),
            "n_models": sum(m.rs_static.n_spline for m in self._meta),
            "bmat_type": self.bmat_kind,
            "bmat_size": int(bsizes.sum()),
            "n_keys": self.n_keys,
            "occupancy": self.n_keys / max(self.capacity, 1),
            "n_shards": self.n_shards,
        }
