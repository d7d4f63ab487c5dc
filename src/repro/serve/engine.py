"""Batched serving engine with an UpLIF-backed prefix-cache index.

Second framework-level integration of the paper's technique: the serving
engine memoizes decode states for previously-seen prompt prefixes. Prefix
fingerprints (rolling hash of token prefixes) form a heavily-updated sparse
key space — every admitted request inserts new fingerprints, evictions
delete them — exactly the updatable-index workload UpLIF targets. Lookups
run batched once per admission wave.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sharded import ShardedUpLIF
from repro.core.uplif import UpLIFConfig
from repro.models.transformer import decode_step, forward_lm, init_cache
from repro.serve.gateway import GatewayConfig, RequestGateway
from repro.tuning import SelfTuner

_MASK = (1 << 52) - 1
_P = 1000003


def prefix_fingerprints(tokens: np.ndarray, every: int = 16) -> np.ndarray:
    """Rolling-hash fingerprints of prefixes at multiples of ``every``."""
    h = np.int64(1469598103)
    out = []
    for i, t in enumerate(tokens.tolist()):
        h = ((h * _P) ^ (t + 0x9E3779B9)) & _MASK
        if (i + 1) % every == 0:
            out.append(h)
    return np.asarray(out, dtype=np.int64)


class PrefixCacheIndex:
    """fingerprint -> cache-slot id, on a sharded UpLIF keyspace router.

    ``capacity_hint`` (expected number of live fingerprints) sizes the
    index: it picks the shard count of the router (one shard per ~2k
    fingerprints, capped at 8) and presizes each shard's delta buffer so
    the steady-state insert path never reallocates. Fingerprints are
    uniform 52-bit hashes, so evenly spaced bootstrap boundaries keep the
    shards balanced from the first admission on.
    """

    def __init__(
        self,
        capacity_hint: int = 4096,
        n_shards: Optional[int] = None,
        tuner: Optional[SelfTuner] = None,
        locate: str = "auto",
    ):
        self.capacity_hint = int(capacity_hint)
        if n_shards is None:
            n_shards = max(1, min(8, self.capacity_hint // 2048))
        # bootstrap keys spread over the fingerprint domain -> balanced
        # shard boundaries (vals -1 = "no slot", never matched)
        n_seed = max(8, 2 * n_shards)
        seed_keys = np.linspace(1, _MASK, n_seed).astype(np.int64)
        per_shard_buf = max(256, self.capacity_hint // max(n_shards, 1))
        # locate="fused" puts the match()/admit() hot path on the fused
        # Pallas locate/rank kernels; "auto" runs the jnp spline path
        self.index = ShardedUpLIF(
            seed_keys,
            np.full(n_seed, -1, dtype=np.int64),
            UpLIFConfig(
                batch_bucket=256, bmat_capacity=per_shard_buf, locate=locate
            ),
            n_shards=n_shards,
        )
        self.slots: Dict[int, Any] = {}
        self._next_slot = 0
        self.hits = 0
        self.misses = 0
        # online self-tuning hook: the tuner observes every fingerprint
        # insert and plans budgeted maintenance when maintain() is called
        # between waves. With an async tuner the build phase overlaps the
        # following serving waves and the rebuilt state lands at a later
        # maintain() (the wave-boundary commit point). Maintenance
        # preserves the fingerprint -> slot mapping either way, so match()
        # results never change — only latency/memory.
        self.tuner = tuner.attach(self.index) if tuner is not None else None
        self._wave_ops = 0
        self._wave_t0 = time.perf_counter()
        self._gateway: Optional[RequestGateway] = None
        self._closed = False
        self._close_lock = threading.Lock()

    def maintain(self):
        """End-of-wave hook: report measured wave throughput to the tuner,
        land any finished background builds, and let it plan the next
        maintenance step. No-op without a tuner."""
        if self.tuner is None:
            return None
        now = time.perf_counter()
        rec = self.tuner.after_wave(self._wave_ops, now - self._wave_t0)
        self._wave_ops = 0
        self._wave_t0 = time.perf_counter()
        return rec

    def open_gateway(
        self, config: Optional[GatewayConfig] = None
    ) -> RequestGateway:
        """Attach (or return the already-open) async request gateway over
        this index's router. The gateway's flusher becomes the router's
        single writer — don't interleave direct match()/admit() waves with
        live gateway traffic. The gateway shares the index's tuner, so
        admission-control pressure sheds the SAME maintenance budget."""
        with self._close_lock:
            if self._closed:
                raise RuntimeError("index is closed")
            if self._gateway is None or self._gateway.closed:
                self._gateway = RequestGateway(
                    self.index, tuner=self.tuner, config=config
                )
            return self._gateway

    def close(self):
        """Drain the gateway (if open), land in-flight builds, persist
        learned Q-tables, stop the executor thread.

        Idempotent AND safe to call concurrently — with other closers and
        with in-flight gateway flushes: the first caller drains everything
        exactly once while later/concurrent callers serialize behind it;
        every already-queued gateway future completes (or fails with
        ``GatewayClosed``), never hangs; submissions racing the close get
        ``GatewayClosed``."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            if self._gateway is not None:
                # joins the flusher: after this, no thread touches the
                # tuner or the router, so the tuner teardown below is safe
                self._gateway.close()
                self._gateway = None
            if self.tuner is not None:
                self.tuner.close()

    def match(self, fps: np.ndarray) -> Tuple[int, int]:
        """Longest cached prefix whose slot is still resident: returns
        (slot_id, n_prefix_blocks) or (-1, 0). A matched-but-evicted slot
        is not a hit — the caller gets (and we count) exactly what it can
        actually reuse, so hits + misses stays consistent with evictions."""
        if len(fps) == 0:
            return -1, 0
        self._wave_ops += len(fps)
        found, slot = self.index.lookup(fps)
        valid = found & (slot >= 0)
        for i in reversed(np.nonzero(valid)[0]):
            sid = int(slot[i])
            if sid in self.slots:
                self.hits += 1
                return sid, int(i) + 1
        self.misses += 1
        return -1, 0

    def admit(self, fps: np.ndarray, state: Any) -> int:
        sid = self._next_slot
        self._next_slot += 1
        self.slots[sid] = state
        if len(fps):
            self._wave_ops += len(fps)
            self.index.insert(fps, np.full(len(fps), sid, dtype=np.int64))
            if self.tuner is not None:
                self.tuner.observe_inserts(fps)
        return sid

    def evict(self, sid: int, fps: np.ndarray):
        self.slots.pop(sid, None)
        if len(fps):
            self._wave_ops += len(fps)
            self.index.delete(fps)

    def memory_bytes(self) -> int:
        return self.index.index_bytes()


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # int32 tokens
    max_new_tokens: int = 16
    out: Optional[List[int]] = None


class ServeEngine:
    """Continuous-batching decode engine (CPU-scale; the sharded production
    path reuses the same decode_step with the dry-run's shardings)."""

    _DEFAULT_TUNER = object()  # sentinel: "make one" vs an explicit None

    def __init__(
        self,
        cfg,
        params,
        max_batch: int = 8,
        max_len: int = 512,
        tuner: Any = _DEFAULT_TUNER,
        async_maintenance: bool = True,
        max_concurrent_builds: int = 2,
        commit_replay_cap: Optional[int] = 4096,
        locate: str = "auto",
    ):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        if tuner is self._DEFAULT_TUNER:
            # self-tuning on unless explicitly disabled; the engine defaults
            # to the async pipeline so index rebuilds overlap decode waves —
            # pass async_maintenance=False to get the stalling sync builds
            # (the config switch bench_self_tuning measures).
            # max_concurrent_builds sizes the maintenance worker pool
            # (disjoint shard rebuilds overlap each other, not just
            # serving) and commit_replay_cap paces each commit's op-log
            # rebase so commit cost per wave stays bounded like every
            # other serving-path op.
            tuner = (
                SelfTuner.overlapped(
                    max_concurrent_builds=max_concurrent_builds,
                    commit_replay_cap=commit_replay_cap,
                )
                if async_maintenance
                else SelfTuner()
            )
        self.prefix_index = PrefixCacheIndex(tuner=tuner, locate=locate)
        self._decode = jax.jit(
            lambda p, tok, cache: decode_step(p, cfg, tok, cache)
        )

    def open_gateway(
        self, config: Optional[GatewayConfig] = None
    ) -> RequestGateway:
        """Async ingestion front end over the engine's prefix index (see
        ``PrefixCacheIndex.open_gateway``)."""
        return self.prefix_index.open_gateway(config)

    def close(self):
        """Idempotent; safe concurrently with in-flight gateway flushes."""
        self.prefix_index.close()

    def _prefill(self, prompt: np.ndarray):
        """Run the prompt through decode steps to build a cache (simple
        token-at-a-time prefill; batched prefill exists in launch/serve)."""
        cache = init_cache(self.cfg, 1, self.max_len)
        logits = None
        for t in prompt.tolist():
            tok = jnp.asarray([[t]], jnp.int32)
            logits, cache = self._decode(self.params, tok, cache)
        return logits, cache

    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a wave of requests (greedy decoding), reusing prefix caches."""
        for req in requests:
            fps = prefix_fingerprints(req.prompt)
            sid, nblk = self.prefix_index.match(fps)
            # match() only returns slots that are still resident
            if sid >= 0:
                cached_len, cache, logits = self.prefix_index.slots[sid]
                tail = req.prompt[cached_len:]
            else:
                cache = init_cache(self.cfg, 1, self.max_len)
                tail = req.prompt
                logits = None
            for t in tail.tolist():
                tok = jnp.asarray([[t]], jnp.int32)
                logits, cache = self._decode(self.params, tok, cache)
            # jax arrays are immutable: the stored cache stays valid even as
            # this request continues decoding from it
            self.prefix_index.admit(fps, (len(req.prompt), cache, logits))
            out = []
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            for _ in range(req.max_new_tokens):
                out.append(int(tok[0, 0]))
                logits, cache = self._decode(self.params, tok, cache)
                tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            req.out = out
        # background maintenance runs between waves, never inside one
        self.prefix_index.maintain()
        return requests
