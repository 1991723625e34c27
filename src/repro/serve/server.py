"""Registration-as-a-service: async batched solve server.

Pipeline (three threads, two depth-1 hand-off queues — the double buffer):

    submit() ──► RequestQueue ──► [batcher] ──► wave queue ──► [solver]
                 (bucketed by      forms waves,  (depth 1)      runs the
                  grid, variant,   stacks host                  vmapped /
                  measure)         arrays, looks                sharded
                                   up warm starts               Newton solve
                                        │
    futures ◄── [collector] ◄── collect queue (depth 1) ◄───────┘
                materializes results, scores mismatch, updates the
                warm-start cache (async checkpoint saves), resolves futures

While wave *k* occupies the device, the batcher is already stacking wave
*k+1* on the host and the collector is materializing wave *k-1* — host-side
ingest and result materialization overlap device solves.

Waves are padded to a fixed width (``max_batch``, repeating the first pair)
so every wave of a bucket reuses one compiled step; per-pair masking inside
``gauss_newton.solve_batch`` already freezes converged lanes, and padded
lanes are simply dropped at collection. Per-bucket compiled steps are built
once and cached — the per-wave cost is the solve, not retracing. On the
single-device path the compiled step donates the wave's velocity buffer
(``_make_batch_step(donate=True)``): the dominant ``(P, 3, N...)`` array is
aliased through each Newton step instead of double-buffered per wave.

Warm starts: requests tagged with a ``subject`` that the
:class:`~repro.serve.cache.WarmStartCache` knows start from the prior
visit's velocity, with the *cold* initial gradient norm as the per-pair
stopping reference (``gnorm_ref``) so convergence is measured against the
same yardstick as the first visit.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from repro import obs
from repro.core import gauss_newton as _gn
from repro.core import metrics as _metrics
from repro.core import registration as _reg

from .batching import BucketKey, PendingRequest, RequestQueue
from .cache import WarmStartCache
from .metrics import ServeStats
from .request import Request, RequestResult

_SENTINEL = object()


@dataclass(frozen=True)
class ServeConfig:
    """Server-level solver + batching knobs (per-request: variant, measure,
    subject)."""

    # dynamic batching
    max_batch: int = 4            # wave width (padding target)
    max_wait_s: float = 0.05      # batching window of a wave's head request
    pad_waves: bool = True        # pad partial waves to max_batch (one
                                  # compiled step per bucket; False trades
                                  # retracing for no padded lanes)
    # solver (Gauss-Newton / transport) configuration shared by all buckets
    nt: int = 4
    beta: float = 5e-4
    gamma: float = 1e-4
    tol_rel_grad: float = 5e-2
    max_newton: int = 20
    backend: str = "jnp"
    mixed_precision: bool = False
    use_plan: bool = True
    use_fused_matvec: bool = False
    # warm-start cache
    warm_start: bool = True
    cache_dir: Optional[str] = None   # persist per-subject velocities
    cache_keep: int = 3               # checkpoint GC: visits kept per subject
    cache_async_io: bool = True
    # slab-distributed waves (repro.distributed): solve each wave with
    # solve_ensemble_slab on this mesh instead of the single-device vmap.
    mesh: object = None
    slab_axis: Optional[str] = None
    ensemble_axis: Optional[str] = None
    halo: int = 6
    halo_compression: str = "none"

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.halo_compression not in ("none", "int8"):
            raise ValueError("halo_compression must be 'none' or 'int8', "
                             f"got {self.halo_compression!r}")
        if self.mesh is not None:
            if not self.pad_waves:
                raise ValueError("mesh serving requires pad_waves=True "
                                 "(fixed wave width)")
            if self.backend not in ("jnp", "pallas"):
                raise ValueError("mesh serving requires backend 'jnp' or "
                                 f"'pallas', got {self.backend!r}")


class _AssembledWave(NamedTuple):
    wave_id: int
    key: BucketKey
    pendings: List[PendingRequest]
    m0: np.ndarray                # (P, N1, N2, N3), P = padded width
    m1: np.ndarray
    v0: np.ndarray                # (P, 3, N1, N2, N3)
    gnorm_ref: np.ndarray         # (P,), NaN = cold (observed reference)
    warm: List[bool]
    visits: List[int]
    t_dispatch: float
    assemble_s: float


class _SolvedWave(NamedTuple):
    wave: _AssembledWave
    result: _gn.BatchGNResult
    v_host: object                # gathered velocity (device array, lazy)
    mismatch: object              # (P,) device array, lazy
    solve_s: float


class Server:
    """Sync in-process serving API; see module docstring for the pipeline.

        with Server(ServeConfig(max_batch=4)) as server:
            fut = server.submit(Request(m0, m1, subject="patient-7"))
            result = fut.result()

    ``submit`` returns a ``concurrent.futures.Future`` (asyncio front ends
    wrap it with ``asyncio.wrap_future``; see
    ``repro.launch.serve_registration``).
    """

    def __init__(self, config: ServeConfig = ServeConfig()):
        self.config = config
        self.stats = ServeStats()
        self.cache = WarmStartCache(
            config.cache_dir, keep=config.cache_keep,
            async_io=config.cache_async_io) if config.warm_start else None
        self._queue = RequestQueue()
        self._wave_q: "queue.Queue" = queue.Queue(maxsize=1)
        self._collect_q: "queue.Queue" = queue.Queue(maxsize=1)
        self._ids = itertools.count()
        self._wave_ids = itertools.count()
        self._steps: Dict = {}        # BucketKey -> compiled Newton step
        self._scorers: Dict = {}      # BucketKey -> jitted mismatch scorer
        self._gn = _gn.GNConfig(
            beta=config.beta, gamma=config.gamma,
            tol_rel_grad=config.tol_rel_grad, max_newton=config.max_newton)
        self._threads: List[threading.Thread] = []
        self._started = False
        self._stopping = False
        if config.mesh is not None:
            from repro.distributed import claire_dist as _dist
            self._slab_axis = (config.slab_axis
                               or _dist.slab_axis_name(config.mesh))
            self._ens_axis = (config.ensemble_axis
                              or _dist.ensemble_axis_name(config.mesh))
            if self._ens_axis is None:
                raise ValueError(
                    f"mesh {config.mesh.axis_names} has no ensemble axis")
            from repro.launch.mesh import axis_size
            ne = axis_size(config.mesh, self._ens_axis)
            if config.max_batch % ne != 0:
                raise ValueError(
                    f"max_batch {config.max_batch} not divisible by "
                    f"ensemble axis {self._ens_axis!r} of size {ne}")

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "Server":
        if self._started:
            return self
        self._started = True
        for name, fn in (("serve-batcher", self._batcher_loop),
                         ("serve-solver", self._solver_loop),
                         ("serve-collector", self._collector_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self):
        """Close ingest, drain queued work, join the pipeline, flush cache."""
        if not self._started or self._stopping:
            return
        self._stopping = True
        self._queue.close()
        for t in self._threads:
            t.join()
        self._threads = []
        if self.cache is not None:
            self.cache.flush()
        self._started = False
        self._stopping = False

    def __enter__(self) -> "Server":
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- client API --------------------------------------------------------

    def submit(self, request: Request) -> Future:
        if not self._started:
            raise RuntimeError("server not started (use start() or a with-block)")
        fut: Future = Future()
        pending = PendingRequest(
            request_id=next(self._ids), request=request, future=fut,
            t_submit=time.perf_counter())
        self._queue.put(pending)
        self.stats.record_submit(pending.t_submit)
        return fut

    def solve(self, request: Request, timeout: Optional[float] = None
              ) -> RequestResult:
        """Blocking convenience: submit and wait."""
        return self.submit(request).result(timeout=timeout)

    def summary(self) -> Dict:
        return self.stats.summary()

    # -- pipeline stage 1: batcher (host assembly) --------------------------

    def _batcher_loop(self):
        c = self.config
        while True:
            wave = self._queue.next_wave(c.max_batch, c.max_wait_s)
            if not wave:
                if self._queue.drained:
                    self._wave_q.put(_SENTINEL)
                    return
                continue
            try:
                assembled = self._assemble(wave)
            except Exception as e:  # malformed inputs must not kill the loop
                for p in wave:
                    p.future.set_exception(e)
                self.stats.record_failure(len(wave))
                continue
            self._wave_q.put(assembled)

    def _assemble(self, wave: List[PendingRequest]) -> _AssembledWave:
        t0 = time.perf_counter()
        c = self.config
        key = wave[0].key
        real = len(wave)
        padded = c.max_batch if c.pad_waves else real
        grid = key.grid

        m0 = np.empty((padded,) + grid, np.float32)
        m1 = np.empty((padded,) + grid, np.float32)
        v0 = np.zeros((padded, 3) + grid, np.float32)
        refs = np.full((padded,), np.nan, np.float64)
        warm: List[bool] = []
        visits: List[int] = []
        for i, p in enumerate(wave):
            m0[i] = np.asarray(p.request.m0, np.float32)
            m1[i] = np.asarray(p.request.m1, np.float32)
            ws = (self.cache.lookup(p.request.subject, grid)
                  if self.cache is not None else None)
            if ws is not None:
                v0[i] = ws.v0
                refs[i] = ws.gnorm_ref
                warm.append(True)
                visits.append(ws.visits)
            else:
                warm.append(False)
                visits.append(0)
        # Padding lanes repeat pair 0 from a cold start; their solves are
        # masked work that keeps the wave shape (and compiled step) fixed.
        for i in range(real, padded):
            m0[i] = m0[0]
            m1[i] = m1[0]
        return _AssembledWave(
            wave_id=next(self._wave_ids), key=key, pendings=wave,
            m0=m0, m1=m1, v0=v0, gnorm_ref=refs, warm=warm, visits=visits,
            t_dispatch=time.perf_counter(),
            assemble_s=time.perf_counter() - t0)

    # -- pipeline stage 2: solver (device) ----------------------------------

    def _transport_cfg(self, key: BucketKey):
        c = self.config
        return _reg.make_transport_config(
            key.variant, nt=c.nt, backend=c.backend,
            mixed_precision=c.mixed_precision, use_plan=c.use_plan,
            measure=key.measure, use_fused_matvec=c.use_fused_matvec)

    def _step_for(self, key: BucketKey):
        step = self._steps.get(key)
        if step is None:
            cfg_t = self._transport_cfg(key)
            if self.config.mesh is not None:
                from repro.distributed import claire_dist as _dist
                step = _dist.make_slab_step(
                    self.config.mesh, cfg_t, self._gn, self._slab_axis,
                    self.config.halo, ens_axis=self._ens_axis,
                    compress=self.config.halo_compression)
            else:
                step = _gn._make_batch_step(cfg_t, self._gn, donate=True)
            self._steps[key] = step
        return step

    def _scorer_for(self, key: BucketKey):
        scorer = self._scorers.get(key)
        if scorer is None:
            import jax
            import jax.numpy as jnp
            cfg_t = self._transport_cfg(key)

            def score(m0b, m1b, vb):
                obs.count_trace("serve_scorer")
                warped = jax.vmap(
                    lambda m, w: _metrics.warp_image(m, w, cfg_t))(m0b, vb)
                num = jnp.sqrt(jnp.sum((warped - m1b) ** 2, axis=(1, 2, 3)))
                den = jnp.sqrt(jnp.sum((m1b - m0b) ** 2, axis=(1, 2, 3)))
                # Identical pairs are already matched: report 0, not NaN/huge.
                return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)

            scorer = self._scorers.setdefault(key, jax.jit(score))
        return scorer

    def _solver_loop(self):
        c = self.config
        while True:
            item = self._wave_q.get()
            if item is _SENTINEL:
                self._collect_q.put(_SENTINEL)
                return
            wave: _AssembledWave = item
            try:
                with obs.span(obs.SOLVE, mode="serve",
                              grid=tuple(wave.m0.shape[1:]),
                              batch=int(wave.m0.shape[0]),
                              sharded=c.mesh is not None):
                    cfg_t = self._transport_cfg(wave.key)
                    step = self._step_for(wave.key)
                    t0 = time.perf_counter()
                    if c.mesh is not None:
                        from repro.distributed import claire_dist as _dist
                        res = _dist.solve_ensemble_slab(
                            wave.m0, wave.m1, cfg_t, self._gn, mesh=c.mesh,
                            ens_axis=self._ens_axis, slab_axis=self._slab_axis,
                            halo=c.halo, v0=wave.v0, gnorm_ref=wave.gnorm_ref,
                            step_fn=step)
                        v_host = _reg._unshard(res.v, c.mesh)
                    else:
                        res = _gn.solve_batch(
                            wave.m0, wave.m1, cfg_t, self._gn, v0=wave.v0,
                            gnorm_ref=wave.gnorm_ref, step_fn=step, donate=True)
                        v_host = res.v
                    # Dispatch scoring asynchronously; the collector forces it
                    # while the solver starts the next wave.
                    mismatch = self._scorer_for(wave.key)(wave.m0, wave.m1, v_host)
                    solve_s = time.perf_counter() - t0
            except Exception as e:
                for p in wave.pendings:
                    p.future.set_exception(e)
                self.stats.record_failure(len(wave.pendings))
                continue
            self._collect_q.put(_SolvedWave(
                wave=wave, result=res, v_host=v_host, mismatch=mismatch,
                solve_s=solve_s))

    # -- pipeline stage 3: collector (materialize + resolve) -----------------

    def _collector_loop(self):
        while True:
            item = self._collect_q.get()
            if item is _SENTINEL:
                return
            solved: _SolvedWave = item
            wave = solved.wave
            res = solved.result
            try:
                t0 = time.perf_counter()
                v = np.asarray(solved.v_host)
                mismatch = np.asarray(solved.mismatch, np.float64)
                real = len(wave.pendings)
                padded = wave.m0.shape[0]
                collect_s = 0.0
                # Stats are recorded BEFORE any future resolves: a client
                # that calls summary() the moment its last result arrives
                # must already see that request (and its wave) counted.
                ready = []
                for i, p in enumerate(wave.pendings):
                    gnorm0_i = float(np.asarray(res.gnorm0)[i])
                    # cache_visits stays the *lookup-time* count (warm-start
                    # provenance); update() already bumps the stored count.
                    cache_visits = wave.visits[i]
                    if self.cache is not None:
                        self.cache.update(
                            p.request.subject, v[i], gnorm0_i, wave.key.grid)
                    t_done = time.perf_counter()
                    collect_s = t_done - t0
                    rr = RequestResult(
                        request_id=p.request_id,
                        subject=p.request.subject,
                        variant=wave.key.variant,
                        grid=wave.key.grid,
                        v=v[i],
                        mismatch_rel=float(mismatch[i]),
                        iters=int(res.iters[i]),
                        matvecs=int(res.matvecs[i]),
                        gnorm0=gnorm0_i,
                        rel_grad=float(res.rel_grad[i]),
                        converged=bool(res.converged[i]),
                        warm_started=wave.warm[i],
                        cache_visits=cache_visits,
                        wave_id=wave.wave_id,
                        wave_real=real,
                        wave_padded=padded,
                        queue_s=wave.t_dispatch - p.t_submit,
                        solve_s=solved.solve_s,
                        collect_s=collect_s,
                        latency_s=t_done - p.t_submit,
                    )
                    self.stats.record_request(
                        dict(request_id=p.request_id, subject=p.request.subject,
                             grid=list(wave.key.grid), variant=wave.key.variant,
                             warm_started=wave.warm[i], iters=rr.iters,
                             matvecs=rr.matvecs, gnorm0=rr.gnorm0,
                             mismatch_rel=rr.mismatch_rel,
                             latency_s=rr.latency_s, queue_s=rr.queue_s,
                             solve_s=rr.solve_s, wave_id=wave.wave_id),
                        t_done=t_done)
                    ready.append((p, rr))
                self.stats.record_wave(dict(
                    wave_id=wave.wave_id, grid=list(wave.key.grid),
                    variant=wave.key.variant, real=real, padded=padded,
                    utilization=real / max(padded, 1),
                    assemble_s=wave.assemble_s, solve_s=solved.solve_s,
                    collect_s=collect_s,
                    iters=[int(x) for x in np.asarray(res.iters)[:real]],
                    warm=list(wave.warm)))
                for p, rr in ready:
                    p.future.set_result(rr)
            except Exception as e:
                for p in wave.pendings:
                    if not p.future.done():
                        p.future.set_exception(e)
                self.stats.record_failure(len(wave.pendings))
