"""Recon-as-a-service: geometry-bucketed dynamic batching for CT requests.

The LM side of the repo serves token streams with fixed decode slots
(:mod:`repro.launch.serve`); this module is the CT analogue.  A scanner farm
produces a stream of small reconstruction jobs, most sharing a handful of
protocol geometries.  The server

  * **buckets** incoming requests by ``(tier, solver, spec.bucket_key(),
    solver kwargs)`` — two requests may share a packed batch iff their
    :class:`~repro.core.spec.ProjectorSpec` hashes equal (same geometry
    content, kernels, mode, precision), so one compiled executable covers
    the whole batch;
  * **packs** same-bucket requests into one batched dispatch: the kernels
    fold ``batch x n_rows`` onto the 128-wide TPU lane axis, so e.g. 128
    single-row 2D recons fill the lanes of a single kernel launch;
  * serves **tiered latency classes** — ``interactive`` (single-shot
    FBP/FDK) is dispatched strictly before ``quality`` (iterative
    sirt / cgls / fista_tv);
  * guarantees a **warm request path**: :meth:`CTServer.warm` primes the op
    cache and the jitted per-(bucket, size-class) executors, and the
    autotuner's disk cache (``~/.cache/repro/tune.json``) is consulted
    before any sweep, so a primed server answers traffic with zero
    compilation and zero autotune sweeps (observable via
    ``repro.kernels.ops.cache_stats`` and ``repro.kernels.tune.sweep_count``);
  * **isolates failures** per request: a request that fails validation or
    crashes its executor is answered with ``ok=False`` and its error
    message — batch mates are re-run individually and still succeed.  The
    failed packed dispatch stays in :attr:`CTServer.dispatch_log` with its
    error, and every re-run is logged as its own size-1 dispatch, so a
    packed executor that never works (a compile failure) cannot pass as
    success.

    >>> srv = CTServer(max_batch=16)
    >>> srv.warm(spec, "fbp")
    >>> rid = srv.submit(ReconRequest(spec=spec, sino=y, solver="fbp"))
    >>> done = srv.drain()
    >>> done[rid].image

Spans (:mod:`repro.runtime.obs`): ``serve.submit`` (with the request's
``rid``), ``serve.step``, ``serve.dispatch`` (with its ``rids``; its
length is the dispatch log's ``wall_s``) and ``serve.queue_wait``, each
request's wait from submit to dispatch (with its ``rid``).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.projector import Projector
from repro.core.spec import ProjectorSpec
from repro.recon import cgls, fista_tv, sirt
from repro.recon.fista_tv import power_iteration
from repro.recon.result import ReconResult
from repro.runtime import obs

__all__ = ["ReconRequest", "ReconResponse", "CTServer", "TIERS",
           "TIER_SOLVERS", "solver_tier"]

# Latency classes, in strict dispatch-priority order.
TIERS = ("interactive", "quality")
TIER_SOLVERS = {
    "interactive": ("fbp",),                      # single-shot FBP / FDK
    "quality": ("sirt", "cgls", "fista_tv"),      # iterative
}
_SOLVERS = {"sirt": sirt, "cgls": cgls, "fista_tv": fista_tv}


def solver_tier(solver: str) -> str:
    for tier, names in TIER_SOLVERS.items():
        if solver in names:
            return tier
    raise ValueError(f"unknown solver {solver!r}; expected one of "
                     f"{sorted(n for v in TIER_SOLVERS.values() for n in v)}")


@dataclasses.dataclass
class ReconRequest:
    """One reconstruction job: a sinogram plus the spec describing its
    operator.  ``solver_kwargs`` must be JSON-canonicalizable scalars
    (``n_iters``, ``beta``, ...) — they are part of the bucket identity,
    since requests in one packed batch share a single compiled solver."""

    spec: ProjectorSpec
    sino: Any
    solver: str = "fbp"
    solver_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    rid: Optional[int] = None                     # assigned at submit()


@dataclasses.dataclass
class ReconResponse:
    rid: int
    ok: bool
    tier: str
    solver: str
    result: Optional[ReconResult] = None          # None iff not ok
    error: Optional[str] = None
    bucket: Optional[str] = None
    batch_size: int = 0                           # real requests in the pack
    latency_s: float = 0.0                        # submit -> answered

    @property
    def image(self):
        return None if self.result is None else self.result.image


def _size_class(n: int, max_batch: int) -> int:
    """Next power of two >= n, capped at max_batch — bounds the number of
    compiled executables per bucket to log2(max_batch)+1."""
    c = 1
    while c < n and c < max_batch:
        c *= 2
    return c


class CTServer:
    """Geometry-bucketed dynamic batcher over the projector stack.

    Synchronous by design (like :class:`repro.launch.serve.Server`): callers
    ``submit`` then ``drain``/``step``.  ``max_batch=1`` degenerates to a
    serial per-request loop — the baseline the throughput bench compares
    against.
    """

    def __init__(self, max_batch: int = 16):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_batch = max_batch
        # bucket key -> FIFO of (request, submit time)
        self._queues: Dict[Tuple, List[Tuple[ReconRequest, float]]] = {}
        self._bucket_meta: Dict[Tuple, ReconRequest] = {}
        # (bucket key, size class) -> jitted executor
        self._executors: Dict[Tuple, Any] = {}
        self._responses: Dict[int, ReconResponse] = {}
        self._next_rid = 0
        #: one record per dispatch: {"bucket", "tier", "solver", "rids",
        #: "size_class", "wall_s", "error", "rerun"} — tests assert
        #: heterogeneous specs never appear in one record.  ``error`` is
        #: the failed packed dispatch's message (None when it ran);
        #: ``rerun`` marks the size-1 re-runs that follow such a failure.
        self.dispatch_log: List[Dict[str, Any]] = []

    # -- admission ---------------------------------------------------------- #
    @staticmethod
    def bucket_key(req: ReconRequest) -> Tuple:
        tier = solver_tier(req.solver)
        kwargs = json.dumps(sorted(req.solver_kwargs.items()), default=float)
        return (tier, req.solver, req.spec.bucket_key(), kwargs)

    def submit(self, req: ReconRequest) -> int:
        """Admit one request.  Validation failures are answered immediately
        (``ok=False``) without ever reaching a batch."""
        rid = self._next_rid if req.rid is None else req.rid
        self._next_rid = max(self._next_rid, rid) + 1
        with obs.span("serve.submit", rid=rid):
            self._admit(dataclasses.replace(req, rid=rid))
        return rid

    def _admit(self, req: ReconRequest) -> None:
        rid = req.rid
        try:
            tier = solver_tier(req.solver)
            if not isinstance(req.spec, ProjectorSpec):
                raise TypeError(f"ReconRequest.spec must be a ProjectorSpec, "
                                f"got {type(req.spec).__name__}")
            expect = req.spec.geom.sino_shape
            if tuple(req.sino.shape) != tuple(expect):
                raise ValueError(f"sinogram shape {tuple(req.sino.shape)} "
                                 f"does not match spec's {tuple(expect)}")
            key = self.bucket_key(req)
        except Exception as e:                    # noqa: BLE001
            self._responses[rid] = ReconResponse(
                rid=rid, ok=False, tier="?", solver=req.solver,
                error=f"{type(e).__name__}: {e}")
            return
        self._queues.setdefault(key, []).append((req, time.perf_counter()))
        self._bucket_meta.setdefault(key, req)

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    # -- executors ---------------------------------------------------------- #
    def _solver_fn(self, req: ReconRequest):
        proj = Projector(req.spec)
        kwargs = dict(req.solver_kwargs)
        if req.solver == "fbp":
            def fn(y):
                img = proj.fbp(y, **kwargs)
                hist = jnp.zeros(y.shape[:-3] + (0,), img.dtype)
                return ReconResult(image=img, iterations=0,
                                   residual_history=hist)
            return fn
        if req.solver == "fista_tv" and "L" not in kwargs:
            # The Lipschitz constant is a property of the operator — compute
            # it once at executor-build time, not inside every traced call.
            kwargs["L"] = float(power_iteration(proj)) * 1.05
        solve = _SOLVERS[req.solver]
        return lambda y: solve(proj, y, **kwargs)

    def _executor(self, key: Tuple, size: int):
        ex = self._executors.get((key, size))
        if ex is None:
            ex = jax.jit(self._solver_fn(self._bucket_meta[key]))
            self._executors[(key, size)] = ex
        return ex

    def warm(self, spec: ProjectorSpec, solver: str = "fbp",
             solver_kwargs: Optional[Dict[str, Any]] = None,
             batch_sizes: Optional[Tuple[int, ...]] = None) -> None:
        """Prime every compiled artifact a bucket's traffic will touch:
        the op cache (kernel matched pairs), the tune registry (reads the
        persisted disk cache if present), and one jitted executor per batch
        size class.  After this, requests for the bucket run with zero
        compiles and zero autotune sweeps."""
        proto = ReconRequest(spec=spec, sino=jnp.zeros(spec.geom.sino_shape),
                             solver=solver,
                             solver_kwargs=dict(solver_kwargs or {}))
        key = self.bucket_key(proto)
        self._bucket_meta.setdefault(key, proto)
        if batch_sizes is None:
            sizes, c = [], 1
            while c <= self.max_batch:
                sizes.append(c)
                c *= 2
            batch_sizes = tuple(sizes)
        for n in batch_sizes:
            size = _size_class(n, self.max_batch)
            y = jnp.zeros((size,) + tuple(spec.geom.sino_shape))
            jax.block_until_ready(self._executor(key, size)(y).image)

    # -- dispatch ----------------------------------------------------------- #
    def _pick_bucket(self) -> Optional[Tuple]:
        """Strict tier priority; FIFO (oldest queued request) within a
        tier so no bucket starves another of the same class."""
        best, best_t = None, None
        for tier in TIERS:                        # priority order
            for key, q in self._queues.items():
                if key[0] != tier or not q:
                    continue
                if best_t is None or q[0][1] < best_t:
                    best, best_t = key, q[0][1]
            if best is not None:
                return best
        return None

    def step(self) -> bool:
        """Dispatch one packed batch (the oldest highest-tier bucket).
        Returns False when no work is queued."""
        key = self._pick_bucket()
        if key is None:
            return False
        with obs.span("serve.step"):
            self._step(key)
        return True

    def _step(self, key: Tuple) -> None:
        q = self._queues[key]
        take, q[:] = q[:self.max_batch], q[self.max_batch:]
        reqs = [r for r, _ in take]
        t_sub = [t for _, t in take]
        tier, solver = key[0], key[1]
        n = len(reqs)
        size = _size_class(n, self.max_batch)
        # Pack on the host: an eager jnp.stack over N tiny device arrays is
        # an N-operand concat whose dispatch overhead (~0.7ms at N=16) would
        # eat the batching win; one numpy stack + a single transfer is flat.
        sinos = [np.asarray(r.sino) for r in reqs]
        batch = np.stack(sinos + [np.zeros_like(sinos[0])] * (size - n))
        t_disp = time.perf_counter()
        for r, ts in take:
            obs.record("serve.queue_wait", ts, t_disp, rid=r.rid)
        with obs.span("serve.dispatch", rids=[r.rid for r in reqs]) as sp:
            try:
                out = self._executor(key, size)(batch)
                # Unpack on the host: per-index device gathers would each
                # compile a tiny executable and poke holes in the warm path.
                img = np.asarray(out.image)
                hist = np.asarray(out.residual_history)
                results: List[Optional[ReconResult]] = [
                    ReconResult(image=img[i], iterations=out.iterations,
                                residual_history=hist[i])
                    for i in range(n)]
                errors: List[Optional[str]] = [None] * n
                batch_error = None
            except Exception as e:                # noqa: BLE001
                batch_error = f"{type(e).__name__}: {e}"
        self._log(key, reqs, size, sp.seconds, batch_error)
        if batch_error is not None:
            # Per-request isolation: re-run the batch members one by one so
            # a single poisoned request cannot take down its batch mates.
            results, errors = [], []
            for r in reqs:
                with obs.span("serve.dispatch", rids=[r.rid],
                              rerun=True) as sp:
                    try:
                        out = self._executor(key, 1)(
                            jnp.asarray(r.sino)[None])
                        results.append(ReconResult(
                            image=np.asarray(out.image)[0],
                            iterations=out.iterations,
                            residual_history=np.asarray(
                                out.residual_history)[0]))
                        errors.append(None)
                    except Exception as e:        # noqa: BLE001
                        results.append(None)
                        errors.append(f"{type(e).__name__}: {e}")
                self._log(key, [r], 1, sp.seconds, errors[-1], rerun=True)
        t1 = time.perf_counter()
        for r, ts, res, err in zip(reqs, t_sub, results, errors):
            self._responses[r.rid] = ReconResponse(
                rid=r.rid, ok=err is None, tier=tier, solver=solver,
                result=res, error=err, bucket=key[2],
                batch_size=n if batch_error is None else 1,
                latency_s=t1 - ts)

    def _log(self, key: Tuple, reqs: List[ReconRequest], size: int,
             wall_s: float, error: Optional[str],
             rerun: bool = False) -> None:
        self.dispatch_log.append({
            "bucket": key[2], "tier": key[0], "solver": key[1],
            "rids": [r.rid for r in reqs], "size_class": size,
            "wall_s": wall_s, "error": error, "rerun": rerun})

    def drain(self) -> Dict[int, ReconResponse]:
        """Run steps until every queued request is answered; returns all
        responses accumulated so far, keyed by rid."""
        while self.step():
            pass
        return dict(self._responses)

    def take_responses(self) -> Dict[int, ReconResponse]:
        out, self._responses = self._responses, {}
        return out
