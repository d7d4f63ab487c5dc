"""UpLIF — the updatable self-tuning learned index (Sections 2–3).

Batched, tensorized realization of the paper's four modules:

  Module 1 (Learned Index Model)  — RadixSpline over the gapped slot array.
  Module 2 (Approximator)         — BMAT delta buffer: bias r(k) = rank of k
                                    among buffered updates; scalier Γ̄ = 1+α.
  Module 3 (Aggregator)           — model prediction + bounded last-mile
                                    window search over the fill-forward-sorted
                                    slot array; in-place inserts with bounded
                                    Movement-K shifting; overflow → BMAT.
  Module 4 (Optimization Agent)   — repro/core/rl_agent.py drives
                                    retrain / subset-retrain / BMAT-type
                                    switches through the hooks on this class.

This class is a *thin stateful shell*: the whole index lives in one
``UpLIFState`` pytree (repro/core/state.py) and every operation forwards to
the jitted pure functions in ``repro/core/fops.py`` — lookup, insert,
delete and range_scan all run end-to-end on device, including the greedy
window-accept (grid-segment formulation) and the fill-forward repair. The
shell owns only host concerns: batch padding, BMAT capacity growth, the
D_update reservoir, and the (host-side, rare) retrain actions.

Every operation takes a *batch* of keys (the TPU-native adaptation; see
DESIGN.md §2). Correctness is property-tested against a host oracle in
tests/test_uplif_invariants.py and tests/test_fops_sharded.py.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro import shapes
from repro.core import fops
from repro.core.bmat import BMAT, BPMAT
from repro.core.gmm import fit_gmm, gmm_memory_bytes, init_gmm_uniform
from repro.core.nullifier import nullify
from repro.core.radix_spline import build_radix_spline, rs_memory_bytes
from repro.core.state import (
    LOCATE_AUTO,
    LOCATE_BINSEARCH,
    LOCATE_STRATEGIES,
    Counters,
    UpLIFState,
    UpLIFStatic,
    init_counters,
    resolve_locate,
)
from repro.core.types import GMMState, KEY_MAX, TOMBSTONE, SlotsState


def join_pieces(parts: List[List[np.ndarray]]) -> List[np.ndarray]:
    """Each query's pieces of a range answer, in pass order, as one array."""
    empty = np.zeros(0, dtype=np.int64)
    return [np.concatenate(p) if len(p) > 1 else (p[0] if p else empty)
            for p in parts]


@dataclasses.dataclass(frozen=True)
class UpLIFConfig:
    """Static knobs (jit-stable)."""

    max_error: int = 24          # ξ — spline error bound
    window: int = 64             # W — last-mile / insert window (power of 2)
    movement_k: int = 6          # K — max elements shifted per insert (§1 Movement)
    d_max: int = 32              # max gap between continuous keys (Eq. 6 cap)
    alpha_target: float = 1.0    # target mean gap α (Eq. 7)
    radix_bits: int = 16
    insert_rounds: int = 3       # in-place retry rounds before BMAT overflow
    batch_bucket: int = 4096     # jit bucket for batched ops
    gmm_components: int = 4
    reservoir: int = 32768       # update-key sample for D_update estimation
    bmat_type: str = BPMAT
    bmat_fanout: int = 16
    bmat_capacity: int = 4096    # initial delta-buffer capacity (grows)
    # locate/rank strategy of the whole index: "auto" is the jnp spline
    # path on every platform; "spline" / "binsearch" / "fused" pin one
    # (fused runs only where the kernels' shape guard admits the state)
    locate: str = LOCATE_AUTO

    def __post_init__(self):
        assert self.window & (self.window - 1) == 0
        assert 2 * (self.max_error + self.movement_k) + 4 <= self.window
        assert self.locate in LOCATE_STRATEGIES + (LOCATE_AUTO,)


# Re-exported from the shared §7.5 quantization module (repro/shapes.py) —
# the shell, the shard router and the serving gateway must bucket
# identically or their jit caches diverge.
bucket_width = shapes.bucket_width


class UpLIF:
    """Batched updatable learned index (thin shell over repro.core.fops)."""

    def __init__(
        self,
        keys: np.ndarray,
        vals: Optional[np.ndarray] = None,
        config: UpLIFConfig = UpLIFConfig(),
        gmm: Optional[GMMState] = None,
    ):
        self.cfg = config
        keys = np.asarray(keys, dtype=np.int64)
        order = np.argsort(keys)
        keys = keys[order]
        if vals is None:
            vals = keys.copy()
        else:
            vals = np.asarray(vals, dtype=np.int64)[order]
        uk, ui = np.unique(keys, return_index=True)
        keys, vals = uk, vals[ui]
        assert np.all(keys >= 0) and (len(keys) == 0 or keys[-1] < KEY_MAX)

        self.bmat = BMAT(
            config.bmat_type, config.bmat_fanout, capacity=config.bmat_capacity
        )
        self._reservoir = np.zeros(0, dtype=np.int64)
        self._rng = np.random.default_rng(0)
        # Section 4.1 counters: usage counters stay on the host; structural
        # counters live in the device-resident Counters pytree.
        self.n_lookups = 0
        self.n_retrains = 0
        self._counters = init_counters()

        if gmm is None:
            lo = float(keys[0]) if len(keys) else 0.0
            hi = float(keys[-1]) if len(keys) else 1.0
            gmm = init_gmm_uniform(lo, hi, config.gmm_components)
        self._bulk_load(keys, vals, gmm)

    # -- construction --------------------------------------------------------
    def _bulk_load(
        self,
        keys: np.ndarray,
        vals: np.ndarray,
        gmm: GMMState,
        alpha_target: Optional[float] = None,
        gap_quantize: str = "ceil",
    ):
        cfg = self.cfg
        self.gmm = gmm
        res = nullify(
            keys,
            vals,
            gmm,
            alpha_target=(
                cfg.alpha_target if alpha_target is None else alpha_target
            ),
            d_max=cfg.d_max,
            tail_slack=max(64, cfg.window),
            align=cfg.window,  # fops grid windows require W-aligned capacity
            quantize=gap_quantize,
        )
        self.slots = res.slots
        self.alpha = res.alpha
        model, static = build_radix_spline(
            keys,
            res.positions,
            radix_bits=cfg.radix_bits,
            max_error=cfg.max_error,
        )
        self.rs_model, self.rs_static = model, static
        c = self._counters
        self._counters = Counters(
            n_keys=jnp.asarray(len(keys), dtype=jnp.int64),
            n_bmat_live=jnp.asarray(self.bmat.live_size, dtype=jnp.int64),
            n_inplace=c.n_inplace,
            n_overflow=c.n_overflow,
            min_granularity=c.min_granularity,
        )

    # -- functional-core plumbing ---------------------------------------------
    @property
    def fstate(self) -> UpLIFState:
        """The whole index as a pure pytree (zero-copy view of the arrays)."""
        return UpLIFState(
            slots=self.slots,
            model=self.rs_model,
            bmat=self.bmat.state,
            counters=self._counters,
        )

    def locate_strategy(self) -> str:
        """Concrete locate strategy for this call: cfg.locate ("auto"
        resolved), held to the fused kernels' guard at the current
        shapes."""
        return resolve_locate(
            self.cfg.locate,
            fops.fused_fits(self.slots.keys, self.rs_model, self.bmat.state),
        )

    def fstatic(self) -> UpLIFStatic:
        """Hashable static config for the fops suite."""
        locate = self.locate_strategy()
        return UpLIFStatic(
            window=self.cfg.window,
            movement_k=self.cfg.movement_k,
            rs_iters=(
                self.rs_static.n_search_iters
                if locate != LOCATE_BINSEARCH
                else 0
            ),
            insert_rounds=self.cfg.insert_rounds,
            fanout=self.bmat.fanout,
            bmat_kind=self.bmat.tree_type,
            locate=locate,
        )

    def _adopt(self, state: UpLIFState):
        self.slots = state.slots
        self.bmat.state = state.bmat
        self._counters = state.counters

    # -- counters (host views of the device pytree) ---------------------------
    @property
    def n_keys(self) -> int:
        return int(self._counters.n_keys)

    @property
    def n_inplace(self) -> int:
        return int(self._counters.n_inplace)

    @property
    def n_overflow(self) -> int:
        return int(self._counters.n_overflow)

    @property
    def min_granularity(self) -> int:
        return int(self._counters.min_granularity)

    @property
    def capacity(self) -> int:
        return int(self.slots.keys.shape[0])

    @property
    def size(self) -> int:
        """Total live keys (in-place + buffered, tombstones excluded)."""
        c = self._counters
        return int(c.n_keys + c.n_bmat_live)

    # -- helpers ---------------------------------------------------------------
    def _pad(self, arr: np.ndarray, fill) -> Tuple[jnp.ndarray, int]:
        """Pad to a bucketed width (see ``bucket_width``) so jit variants
        stay few while retry rounds on small leftovers avoid full-batch
        work."""
        n = len(arr)
        m = bucket_width(n, self.cfg.batch_bucket)
        if n == m:
            return jnp.asarray(arr), n
        out = np.full(m, fill, dtype=arr.dtype)
        out[:n] = arr
        return jnp.asarray(out), n

    def _ensure_bmat_capacity(self, incoming: int):
        """Pure-fn merges cannot grow arrays: presize for the worst case
        (every incoming key overflows) before entering the jitted insert."""
        if self.bmat.size + incoming > self.bmat.capacity - 1:
            self.bmat._grow(self.bmat.size + incoming)

    # -- queries ---------------------------------------------------------------
    def lookup(self, queries: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Batched point lookup → (found bool[n], values int64[n])."""
        queries = np.asarray(queries, dtype=np.int64)
        q, n = self._pad(queries, KEY_MAX)
        alive, vals = fops.lookup(self.fstate, q, static=self.fstatic())
        self.n_lookups += n
        return np.asarray(alive)[:n], np.asarray(vals)[:n]

    def adjusted_predict(self, queries: np.ndarray) -> np.ndarray:
        """Paper Eq. 1 / Module 3: logical position M'(k) = Γ̄·M(k) + r(k),
        where Γ̄ = 1/(1+α) maps slot space back to logical rank space and
        r(k) is the BMAT bias (Phase 1). Exposed for validation."""
        queries = np.asarray(queries, dtype=np.int64)
        q, n = self._pad(queries, KEY_MAX)
        rank = fops.adjusted_rank(self.fstate, q, static=self.fstatic())
        return np.asarray(rank)[:n]

    def range_query(self, lo: int, hi: int, max_out: int = 1024):
        """Sorted (keys, vals) with lo <= key <= hi (single range; batched
        variant used by benchmarks lives in range_query_batch)."""
        ks, vs = self.range_query_batch(
            np.asarray([lo], dtype=np.int64),
            np.asarray([hi], dtype=np.int64),
            max_out,
        )
        return ks[0], vs[0]

    def range_query_batch(self, lo: np.ndarray, hi: np.ndarray, max_out: int = 1024):
        """Batched range extraction. Each pass is ONE jitted program
        (vmapped fixed-width slice + masked BMAT merge, fops.range_scan);
        a query whose slices ran short (tombstones, gaps) continues from
        its front in a further pass, so every answer is whole."""
        lo = np.asarray(lo, dtype=np.int64)
        hi = np.asarray(hi, dtype=np.int64)
        parts_k: List[List[np.ndarray]] = [[] for _ in lo]
        parts_v: List[List[np.ndarray]] = [[] for _ in lo]
        qi, cur = np.arange(len(lo)), lo
        left = np.full(len(lo), max_out, dtype=np.int64)
        while len(qi):
            ql, n = self._pad(cur, KEY_MAX)
            qh, _ = self._pad(hi[qi], 0)
            qn, _ = self._pad(left[qi], 0)
            res = fops.range_scan(
                self.fstate, ql, qh, qn, static=self.fstatic(), max_out=max_out
            )
            ks, vs, cn, covered, front = (np.asarray(x)[:n] for x in res)
            for i, q in enumerate(qi):
                parts_k[q].append(ks[i, : cn[i]])
                parts_v[q].append(vs[i, : cn[i]])
            left[qi] -= cn
            more = ~covered
            if np.any(front[more] <= cur[more]):
                raise RuntimeError("range scan made no progress")
            qi, cur = qi[more], front[more]
        return join_pieces(parts_k), join_pieces(parts_v)

    # -- updates ---------------------------------------------------------------
    def insert(self, keys: np.ndarray, vals: Optional[np.ndarray] = None):
        """Batched upsert. Returns count that went to the BMAT overflow."""
        keys = np.asarray(keys, dtype=np.int64)
        if vals is None:
            vals = keys.copy()
        vals = np.asarray(vals, dtype=np.int64)
        assert keys.shape == vals.shape
        if len(keys) == 0:
            return 0
        self._observe_updates(keys)
        q, _ = self._pad(keys, KEY_MAX)
        v, _ = self._pad(vals, 0)
        self._ensure_bmat_capacity(int(q.shape[0]))
        state, res = fops.insert(self.fstate, q, v, static=self.fstatic())
        self._adopt(state)
        return int(res.n_overflow)

    def delete(self, keys: np.ndarray) -> np.ndarray:
        """Batched delete (tombstones; compacted at retrain). Returns hits."""
        keys = np.asarray(keys, dtype=np.int64)
        q, n = self._pad(keys, KEY_MAX)
        state, hit = fops.delete(self.fstate, q, static=self.fstatic())
        self._adopt(state)
        return np.asarray(hit)[:n]

    # -- D_update estimation (Phase 2) ----------------------------------------
    def _observe_updates(self, keys: np.ndarray):
        cap = self.cfg.reservoir
        take = keys if len(keys) <= cap else self._rng.choice(keys, cap, replace=False)
        self._reservoir = np.concatenate([self._reservoir, take])
        if len(self._reservoir) > cap:
            self._reservoir = self._rng.choice(self._reservoir, cap, replace=False)

    def refreshed_gmm(self) -> GMMState:
        if len(self._reservoir) >= 64:
            return fit_gmm(
                jnp.asarray(self._reservoir, dtype=jnp.float64),
                self.cfg.gmm_components,
            )
        return self.gmm

    # -- tuning actions (Section 4.2) ------------------------------------------
    def extract_live(self) -> Tuple[np.ndarray, np.ndarray]:
        """All live (key, value) pairs — in-place + buffered, tombstones
        dropped — sorted by key. The raw material of every structural
        action (retrain, shard split/merge)."""
        sk = np.asarray(self.slots.keys)
        sv = np.asarray(self.slots.vals)
        so = np.asarray(self.slots.occ)
        live = so & (sv != TOMBSTONE)
        ak, av = sk[live], sv[live]
        bk, bv = self.bmat.extract()
        keys = np.concatenate([ak, bk])
        vals = np.concatenate([av, bv])
        o = np.argsort(keys, kind="stable")
        return keys[o], vals[o]

    def retrain_full(
        self,
        gmm: Optional[GMMState] = None,
        alpha_target: Optional[float] = None,
        gap_quantize: str = "ceil",
    ):
        """Action: full retrain — flush BMAT, drop tombstones, re-nullify with
        the refreshed D_update estimate, rebuild the spline. ``gmm`` lets a
        caller supply an external D_update forecast (the online tuning
        subsystem's streaming estimate) instead of the reservoir refit, so
        Eq. 6 gaps are sized for *predicted* — not just observed — inserts;
        ``alpha_target`` overrides the Eq. 7 gap budget (the sharded router
        fits it to available capacity so absorbs reuse compiled shapes)."""
        keys, vals = self.extract_live()
        self.bmat = BMAT(
            self.bmat.tree_type, self.cfg.bmat_fanout,
            capacity=self.cfg.bmat_capacity,
        )
        self._bulk_load(
            keys, vals,
            gmm if gmm is not None else self.refreshed_gmm(),
            alpha_target=alpha_target,
            gap_quantize=gap_quantize,
        )
        self.n_retrains += 1

    def retrain_subset(self, quantiles: int = 16) -> int:
        """Action: retrain on a data subset — absorb the densest BMAT key
        range back in place (multi-round window inserts), shrinking the BMAT
        without touching the rest of the index. Returns #absorbed."""
        if self.bmat.size == 0:
            return 0
        bk, bv = self.bmat.extract()
        if len(bk) == 0:
            return 0
        qs = np.quantile(bk, np.linspace(0, 1, quantiles + 1)).astype(np.int64)
        counts = np.histogram(bk, bins=qs)[0]
        b = int(np.argmax(counts))
        lo, hi = int(qs[b]), int(qs[b + 1])
        m = (bk >= lo) & (bk <= hi)
        ck, cv = bk[m], bv[m]
        if len(ck) == 0:
            return 0
        q, nf = self._pad(ck, KEY_MAX)
        v, _ = self._pad(cv, 0)
        state, res = fops.insert(
            self.fstate, q, v, static=self.fstatic(),
            check_bmat=False, merge_overflow=False,
        )
        self._adopt(state)
        absorbed_mask = ~np.asarray(res.pending)[:nf]
        absorbed = int(absorbed_mask.sum())
        if absorbed > 0:
            keys_all, vals_all = self.bmat.extract()
            keep = ~np.isin(keys_all, ck[absorbed_mask])
            self.bmat._rebuild(keys_all[keep], vals_all[keep])
            self._counters = self._counters._replace(
                n_bmat_live=jnp.asarray(int(keep.sum()), dtype=jnp.int64)
            )
        self.n_retrains += 1
        return absorbed

    def switch_bmat_type(self):
        self.bmat.switch_type()

    # -- accounting (Sections 4.1 / 5.5) ---------------------------------------
    def memory_bytes(self, modeled: bool = False) -> int:
        slots = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a in self.slots
        )
        return (
            slots
            + self.bmat.memory_bytes(modeled)
            + rs_memory_bytes(self.rs_model)
            + gmm_memory_bytes(self.gmm)
        )

    def index_bytes(self, modeled: bool = False) -> int:
        """Index-structure-only footprint (excludes the key/value payload
        slots — this is the §5.5 'index memory size' the paper reports)."""
        return (
            self.bmat.memory_bytes(modeled)
            + rs_memory_bytes(self.rs_model)
            + gmm_memory_bytes(self.gmm)
        )

    def measures(self) -> dict:
        """Section 4.1 performance measures (RL state features)."""
        occ_frac = self.n_keys / max(self.capacity, 1)
        return {
            "bmat_height": self.bmat.height,
            "granularity": int(self.min_granularity),
            "error_scaling": float(self.alpha),
            "n_models": int(self.rs_static.n_spline),
            "bmat_type": self.bmat.tree_type,
            "bmat_size": self.bmat.size,
            "n_keys": self.n_keys,
            "occupancy": occ_frac,
        }
