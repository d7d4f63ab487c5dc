"""One run of one cell: set the deployment up, drive it through the gateway
in a closed loop, measure the window, then check every answer.

Order of a run (the window starts at the first timed request; all that
comes before it is set-up, ``setup_s``):

1. the configuration's key set (``bench/keysets/<keyset>.py``, fixed by
   its ``data_seed`` as SOSD's files are fixed, so every seed serves the
   same shapes and compiles the same programs); bootstrap values and the
   request stream (the mix's op kinds, ``bench/ops/<op>.py``) from
   ``--seed``;
2. the deployment as a user builds it: ``ShardedUpLIF`` bulk load, an
   overlapped ``SelfTuner`` attached to it, a ``RequestGateway`` over both;
3. one no-op wave per (op kind, pad width) the cell's traffic can reach,
   then the cell's own traffic, untimed, for ``WARM_WAVES`` waves and at
   least ``WARM_MIN_S`` seconds: the same amount of work in every run, so
   the window starts from a deployment of the same age. The tuner's first
   decisions (a BMAT switch, a delta buffer presized for the forecast
   inserts) fall in it. The program keeps compiling small eager programs
   whose shapes follow the data, so no warm-up ends them all:
   ``compiles_in_window`` counts those that fall in the window;
4. the window: the same closed loop, ``seconds`` long, traced with
   ``--trace 1``;
5. every answer still due is awaited; the chip's peak memory is read; a
   sample of the keys that op kinds marked ``WRITES`` wrote is read back
   through the gateway;
6. the reference replays every request in wave order (``reference.py``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from yardstick import keysets, spec, trace as tr
from yardstick.compiles import CompileClock
from yardstick.loop import ClosedLoop
from yardstick.reference import OK, Reference, check_answers
from yardstick.traffic import VALUE_LIMIT, make_stream

WARM_MIN_S = 3.0        # untimed traffic before the window, at least
WARM_WAVES = 24         # ... and at least this many waves (6 tuner decisions)
WARM_CAP_S = 60.0       # ... but no longer than this
STREAM_LEN = 1 << 20    # requests drawn per seed (the loop wraps around)
FINISH_TIMEOUT_S = 60.0  # wait for answers due at the window's close
READ_BACK_MAX = 16384   # written keys read back after the window
LIMITS = {"wrong_answers": 0, "missing_answers": 0}


def log(msg: str):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class Measured:
    """What a run saw in its window; the per-layer readers take this."""

    t0: float                        # window, host perf_counter seconds
    t1: float
    rows: Dict[str, np.ndarray]      # the loop's columns, one row a request
    gw_before: dict                  # gateway stats at the window's ends
    gw_after: dict
    spans: Dict[str, List[tuple]]    # benchmark spans (perf_counter) by layer
    compiles: List[tuple]            # programs compiled or loaded in window
    trace: Optional[tr.Trace] = None

    def done_in_window(self) -> np.ndarray:
        r = self.rows
        return (r["status"] == OK) & (r["t_done"] >= self.t0) & (r["t_done"] < self.t1)

    def spans_in_window(self, layer: str) -> List[tuple]:
        return [(a, b) for a, b in self.spans.get(layer, ())
                if self.t0 <= a < self.t1]


@dataclasses.dataclass
class Deployment:
    gw: object
    index: object
    close: Callable[[], None]


def build_program(config: dict, keys: np.ndarray, vals: np.ndarray) -> Deployment:
    """The deployment as ``examples/serve_gateway.py`` sets it up."""
    from repro.core import ShardedUpLIF
    from repro.serve import GatewayConfig, RequestGateway
    from repro.tuning import (ControllerConfig, SchedulerConfig, SelfTuner,
                              TunerConfig)

    index = ShardedUpLIF(keys, vals, n_shards=config["n_shards"])
    log(f"slots {index.state.slots.keys.shape}, delta buffer "
        f"{index.state.bmat.keys.shape}, locate {index.shard_locate()}")
    overlapped = dict(config["tuner"])
    tuner_cfg = TunerConfig(
        controller=ControllerConfig(**overlapped.pop("controller", {})),
        scheduler=SchedulerConfig(**overlapped.pop("scheduler", {})))
    tuner = SelfTuner.overlapped(tuner_cfg, **overlapped).attach(index)
    gw = RequestGateway(index, tuner=tuner,
                        config=GatewayConfig(**config["gateway"]))

    def close():
        try:
            gw.close()
        finally:
            tuner.close()

    return Deployment(gw=gw, index=index, close=close)


def _rngs(seed: int):
    """Independent generators for bootstrap values, traffic and sampling."""
    ss = np.random.SeedSequence(seed % (1 << 64))
    return [np.random.default_rng(s) for s in ss.spawn(3)]


def _pad_widths(gw) -> List[int]:
    """The flush widths the gateway can pad a wave to."""
    lo, hi = gw.cfg.min_pad, max(gw.cfg.max_batch, gw.cfg.min_pad)
    widths = [lo]
    while widths[-1] < hi:
        widths.append(widths[-1] * 2)
    return widths


def prime(dep: Deployment, ops: tuple, key: int, val: int):
    """One wave per pad width with every op kind the mix sends, each on a
    stored key and leaving the state as it was (``prime`` of its file)."""
    from repro.core.sharded import MixedWave

    for w in _pad_widths(dep.gw):
        fields = {}
        for op in ops:
            for k, v in op.prime(key, val, w).items():
                fields.setdefault(k, v)
        dep.index.apply_wave(MixedWave(**fields))


def install_spans(dep: Deployment, spans: Dict[str, List[tuple]]):
    """Time the calls into the router and the tuner, on the host clock and
    as profiler annotations (traced runs only)."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, attr: str, layer: str):
        inner = getattr(obj, attr)
        out = spans.setdefault(layer, [])

        def timed(*a, **kw):
            t = time.perf_counter()
            with TraceAnnotation(tr.SPAN_PREFIX + layer):
                r = inner(*a, **kw)
            out.append((t, time.perf_counter()))
            return r

        setattr(obj, attr, timed)

    wrap(dep.index, "apply_wave", "router")
    tuner = getattr(dep.gw, "tuner", None)
    if tuner is not None:
        wrap(tuner, "after_wave", "tuner")
        wrap(tuner, "observe_inserts", "tuner")


def _device_info():
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peaks) if peaks else None,
    }


def end_to_end(m: Measured, setup_s: float, peak: Optional[int],
               live_keys: int) -> Dict[str, Optional[float]]:
    return {
        "ops_per_s": m.done_in_window().sum() / (m.t1 - m.t0),
        "hbm_bytes_per_key": peak / live_keys if peak else None,
        "setup_s": setup_s,
    }


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             build: Callable = build_program,
             trace_dir: Optional[str] = None) -> dict:
    import jax

    import repro.core  # noqa: F401 -- 64-bit keys and values

    cfg, mix = cell.config, cell.traffic
    clock = CompileClock()
    val_rng, traffic_rng, sample_rng = _rngs(seed)
    keys = keysets.make_keys(cell.bench_dir, cfg["keyset"], int(cfg["n_keys"]),
                             int(cfg["data_seed"]))
    vals = val_rng.integers(0, VALUE_LIMIT, len(keys), dtype=np.int64)
    stream = make_stream(mix, cell.ops, keys, traffic_rng, STREAM_LEN)
    # the read-back after the window sends the ``read`` op kind
    read = spec.load_module(cell.bench_dir, "ops", "read")
    ops = cell.ops if read in cell.ops else cell.ops + (read,)
    read_op = ops.index(read)
    log(f"{cell.name}: {len(keys):,} {cfg['keyset']} keys, stream of "
        f"{len(stream):,} requests, {time.perf_counter() - t_start:.3f}s")

    dep = build(cfg, keys, vals)
    spans: Dict[str, List[tuple]] = {}
    try:
        log(f"built in {time.perf_counter() - t_start:.3f}s")
        prime(dep, cell.ops, int(keys[0]), int(vals[0]))
        loop = ClosedLoop(dep.gw, stream, ops, int(mix["clients"]))
        loop.start()
        t_warm = time.perf_counter()
        loop.pump(t_warm + WARM_MIN_S)
        while (dep.gw.n_waves < WARM_WAVES
               and time.perf_counter() - t_warm < WARM_CAP_S):
            loop.pump(time.perf_counter() + 0.25)
        log(f"warm: {loop.n:,} requests, {dep.gw.n_waves} waves in "
            f"{time.perf_counter() - t_warm:.3f}s, "
            f"{clock.count} programs compiled or loaded, compile "
            f"{clock.seconds:.3f}s")

        tdir = None
        if trace:
            install_spans(dep, spans)
            tdir = trace_dir or os.path.join(os.getcwd(), ".bench_trace")
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        gw_before = dep.gw.stats()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + tr.WINDOW):
            loop.pump(t0 + seconds)
            t1 = time.perf_counter()
        gw_after = dep.gw.stats()
        missing = loop.finish(FINISH_TIMEOUT_S)
        measured = Measured(
            t0=t0, t1=t1, rows=loop.columns(),
            gw_before=gw_before, gw_after=gw_after, spans=spans,
            compiles=clock.between(t0, t1))
        if trace:
            jax.profiler.stop_trace()
            (pb,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            measured.trace = tr.from_xspace(pb)
            shutil.rmtree(tdir, ignore_errors=True)
        device = _device_info()
        live_keys = int(getattr(dep.index, "size", len(keys)))
        log(f"window: {int(measured.done_in_window().sum()):,} answers in "
            f"{t1 - t0:.3f}s; {missing} never came; peak "
            f"{device['memory_peak_bytes']} B, {live_keys:,} live keys; "
            f"{len(measured.compiles)} programs compiled or loaded in it "
            f"{sorted(set(c[3] for c in measured.compiles))}")
        log(f"gateway {dep.gw.stats()}")
        tuner = getattr(dep.gw, "tuner", None)
        if tuner is not None:
            log(f"tuner {tuner.stats()}")

        rows = measured.rows
        writes = [i for i, op in enumerate(ops) if getattr(op, "WRITES", False)]
        written = np.unique(rows["key"][np.isin(rows["op"], writes)
                                        & (rows["status"] == OK)])
        if len(written):
            pick = sample_rng.choice(written, min(len(written), READ_BACK_MAX),
                                     replace=False)
            loop.read(read_op, pick, FINISH_TIMEOUT_S)
    finally:
        dep.close()

    checks = check_answers(Reference(keys, vals), loop.records(), ops)
    correct = all(checks[k] <= LIMITS[k] for k in LIMITS)
    result = {
        "correct": bool(correct),
        "attempted": int(loop.n),
        "failed": int(sum(checks.values())),
    }
    if trace:
        result["metrics"] = spec.read_metrics(cell, measured)
        t = measured.trace
        device["busy_s"] = tr.busy_seconds(t)
        device["window_s"] = tr.window_seconds(t)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.top_ops(t)],
            "idle_gaps": [[n, s] for n, s in tr.idle_gaps(t)],
        }
    else:
        values = end_to_end(measured, t0 - t_start, device["memory_peak_bytes"],
                            live_keys)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end if values.get(m["name"]) is not None
        }
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]} for k, v in checks.items()}
    return result
