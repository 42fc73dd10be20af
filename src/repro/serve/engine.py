"""Batched serving engines: continuous batching with static shapes.

Two workloads share the same philosophy (static shapes, one fused device call
per round, queue-fed slots):

* ``Engine`` — token serving.  Requests queue up; up to ``max_batch`` live in
  fixed KV-cache slots with *per-slot positions* (decode_step takes a (b,)
  position vector).  Every round issues ONE batched decode step: prefilling
  slots feed their next prompt token, generating slots feed their last sampled
  token, finished slots are refilled from the queue.  Greedy sampling; the
  padded-vocab tail is masked at sample time.

* ``SVDEngine`` — spectral serving over the batch-native SVD pipeline.
  Requests are bucketed by compilation key ``(n, bw, dtype, banded,
  compute_uv)``; each flush pads one bucket to the config's ``max_batch``
  and issues ONE batched pipeline call (``core.svd.svd_batched``, in
  reflector-tape mode for ``compute_uv`` buckets), so heavy small-matrix
  traffic saturates the chase wavefront that a single matrix cannot (paper
  Eq. 1).  Padding keeps shapes static — one compilation per bucket key,
  ever.

The asynchronous tier (thread-safe queue, micro-batch window, futures,
deadlines, mesh dispatch) lives in ``serve/async_engine.py`` and extends
``SVDEngine``; metrics counters shared by both live in
``serve/metrics.py`` (DESIGN.md §12).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serve.faults import BucketQuarantine, RetryPolicy
from repro.serve.metrics import ServeMetrics, bucket_key_str

__all__ = ["Request", "ServeConfig", "Engine",
           "SVDRequest", "SVDEngine"]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    frames: np.ndarray | None = None          # enc-dec (whisper) stub input
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 4
    max_seq: int = 128
    eos_id: int = -1                          # -1: never stop early


class _Slot:
    __slots__ = ("req", "pos", "k", "next_tok")

    def __init__(self, req):
        self.req = req
        self.pos = 0                          # next cache position to write
        self.k = 0                            # prompt cursor
        self.next_tok = req.prompt[0]


class Engine:
    def __init__(self, model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self._decode = jax.jit(model.decode_step)
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.slots: list[_Slot | None] = [None] * cfg.max_batch
        self.caches = model.init_caches(cfg.max_batch, cfg.max_seq)
        self._is_encdec = model.cfg.kind == "encdec"
        if self._is_encdec:
            d = model.cfg.d_model
            self._frames = np.zeros((cfg.max_batch, model.cfg.enc_seq, d),
                                    np.float32)

    def submit(self, req: Request):
        assert len(req.prompt) >= 1
        self.queue.append(req)

    def _admit(self):
        refreshed = False
        for i in range(self.cfg.max_batch):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                self.slots[i] = _Slot(req)
                if self._is_encdec:
                    fr = req.frames if req.frames is not None else 0.0
                    self._frames[i] = fr
                    refreshed = True
        if refreshed:
            from repro.models.encdec import fill_cross_cache
            self.caches = fill_cross_cache(
                self.params, self.model.cfg, jnp.asarray(self._frames),
                self.caches)

    def step(self) -> int:
        """One batched decode round.  Returns number of active slots."""
        self._admit()
        act = [i for i, s in enumerate(self.slots) if s is not None]
        if not act:
            return 0
        b = self.cfg.max_batch
        toks = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        for i in act:
            s = self.slots[i]
            toks[i, 0] = s.next_tok
            pos[i] = s.pos
        logits, self.caches = self._decode(
            self.params, jnp.asarray(toks), self.caches, jnp.asarray(pos))
        v = self.model.cfg.vocab
        nxt = np.asarray(jnp.argmax(logits[:, 0, :v], axis=-1))
        for i in act:
            s = self.slots[i]
            s.pos += 1
            s.k += 1
            if s.k < len(s.req.prompt):           # still prefilling
                s.next_tok = int(s.req.prompt[s.k])
                continue
            tok = int(nxt[i])
            s.req.output.append(tok)
            s.next_tok = tok
            if (tok == self.cfg.eos_id
                    or len(s.req.output) >= s.req.max_new_tokens
                    or s.pos >= self.cfg.max_seq - 1):
                s.req.done = True
                self.finished.append(s.req)
                self.slots[i] = None
        return len(act)

    def run(self, max_rounds: int = 10_000) -> list[Request]:
        rounds = 0
        while (self.queue or any(self.slots)) and rounds < max_rounds:
            self.step()
            rounds += 1
        return self.finished


# ---------------------------------------------------------------------------
# Batched SVD serving (shape-bucketed, batch-native pipeline)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SVDRequest:
    """One spectral query: singular values (and optionally vectors) of a
    square (or banded) matrix.

    A request always COMPLETES (``done=True``) exactly once: either with
    results (``sigma`` and, for ``compute_uv``, ``u``/``vt``) or with
    ``error`` set to the exception that failed it — engines never raise a
    per-request problem out of a whole batched step.  ``deadline`` (an
    absolute ``time.monotonic()`` instant) is honored by the async engine:
    a request still queued past its deadline is failed with
    :class:`TimeoutError` instead of being dispatched.
    """
    uid: int
    matrix: np.ndarray                         # (n, n); upper-banded if banded
    bw: int = 32                               # stage-1 target / band bandwidth
    banded: bool = False                       # True: skip stage 1
    compute_uv: bool = False                   # True: full SVD (U, sigma, Vt)
    sigma: np.ndarray | None = None            # (n,) result, descending
    u: np.ndarray | None = None                # (n, n) left vectors (compute_uv)
    vt: np.ndarray | None = None               # (n, n) right vectors^T
    done: bool = False
    error: Exception | None = None             # set instead of raising
    deadline: float | None = None              # absolute monotonic() instant
    arrived: float | None = None               # set at submit (monotonic())
    future: object | None = dataclasses.field(default=None, repr=False)

    def key(self) -> tuple:
        """Bucket/compilation key: everything that shapes the pipeline.

        ``compute_uv`` is part of the key — the tape-mode pipeline is a
        different compiled program (and a values-only request must not pay
        for a co-bucketed full-SVD one).
        """
        return (self.matrix.shape[-1], self.bw, np.dtype(self.matrix.dtype).name,
                self.banded, self.compute_uv)


class SVDEngine:
    """Shape-bucketing batched SVD server.

    Queued requests are grouped by ``SVDRequest.key()``; ``step`` flushes the
    fullest bucket as ONE batched pipeline call, padded to the bucket capacity
    (``PipelineConfig.max_batch``) so every key compiles exactly once.  Results
    are numerically identical to a direct ``svd_batched`` call on the same
    stack — padding rows are independent problems and are sliced off.

    >>> eng = SVDEngine(PipelineConfig.resolve(bw=8, dtype=np.float64))
    >>> eng.submit(SVDRequest(uid=0, matrix=a, bw=8))
    >>> done = eng.run()

    ``autotune=True`` resolves each bucket's pipeline against the
    persistent tuned-config cache (DESIGN.md §11): the first flush of a
    bucket key looks up the measured optimum for that exact ``(device, n,
    bw, dtype, compute_uv, backend)``.  Precedence is explicit: opting in
    means a cache HIT overrides the engine config's ``tw`` for
    that bucket — per-bucket measured optima are the point of the flag,
    and the engine config's knobs were resolved for its own default
    shape, not this bucket's; on a MISS the engine's own config stays in
    charge (it is never silently swapped for the analytic defaults).  Pin
    knobs for every bucket by keeping ``autotune=False`` (the default).
    The resolved config is memoized per key (one lookup — and one jit
    compilation — per bucket, ever).  The engine-level ``max_batch``
    stays a hard CAP either way.

    ``mesh`` (a ``jax.sharding.Mesh`` with a ``"data"`` axis, e.g. from
    ``repro.launch.mesh.serve_mesh()``) switches every batched dispatch to
    the multi-device path: the padded bucket is batch-sharded through
    ``core.distributed.sharded_pipeline_dispatch`` so one engine saturates
    all local devices (DESIGN.md §12).  ``metrics`` (a
    :class:`~repro.serve.metrics.ServeMetrics`) counts queue depth,
    batch-fill ratio, and bucket hit-rate.

    ``fused_n_max`` governs the one-dispatch fused small-n tier
    (DESIGN.md §13): buckets with ``n <= fused_n_max`` resolve with
    ``backend="fused_small"`` — the whole per-matrix pipeline as a single
    kernel dispatch — and everything larger stays on the staged pipeline.
    ``None`` (the default) uses the tuned crossover from the cache when
    ``autotune=True``, else ``tuning.DEFAULT_FUSED_CROSSOVER``; ``0``
    disables the tier; an int pins it.  Per-bucket routing is visible in
    ``metrics.snapshot()["bucket_tiers"]`` and the dispatch counters in
    ``["tiers"]`` — the serve smoke gate asserts on both.

    ``dc_n_min`` is the same idea at the other end of the size axis
    (DESIGN.md §14): staged buckets with ``n >= dc_n_min`` resolve with
    the divide-and-conquer stage 3 (``stage3="dc"``) instead of the
    O(n^2-iteration) Sturm bisection, so the bidiagonal solve stops
    dominating large-n serve latency.  ``None`` (the default) uses the
    measured crossover persisted by ``python -m repro.autotune
    --stage3-crossover`` when ``autotune=True``, else
    ``core.bidiag_dc.DEFAULT_DC_N_MIN``; ``0`` disables the D&C tier
    (every bucket bisects); an int >= 1 pins the crossover.  Routing shows
    up as the ``"staged-dc"`` tier in the same metrics surfaces.

    **Fault tolerance (DESIGN.md §15).**  Every dispatched result passes
    the numerical-health guard (``core.svd.validate_sigma`` + vector
    finiteness; ``residual_check=True`` adds the per-batch residual
    spot-check for ``compute_uv`` buckets) — a NaN-producing chase raises
    ``NumericalFault`` instead of returning garbage.  A failed dispatch
    enters the ``retry`` ladder (:class:`~repro.serve.faults.RetryPolicy`:
    bounded attempts, capped exponential backoff, deadline-aware — a
    backoff that would outlive the request's deadline is never slept);
    exhausted requests are re-served on the DEGRADED tier — the bucket's
    shape on the trusted ``ref`` backend with the bisection stage 3 —
    attributed as ``"degraded-ref"`` in the metrics.  Repeated
    primary-path failures trip the bucket's circuit breaker
    (:class:`~repro.serve.faults.BucketQuarantine`): an OPEN bucket routes
    straight to the degraded tier until the cooldown elapses, then one
    HALF-OPEN primary trial decides recovery.  ``faults`` (a
    :class:`~repro.serve.faults.FaultPlan`) injects deterministic
    failures into the primary path for testing; the degraded tier is
    never injected.
    """

    def __init__(self, config=None, *, backend: str = "auto",
                 max_batch: int | None = None, autotune: bool = False,
                 autotune_cache: str | None = None, mesh=None,
                 fused_n_max: int | None = None,
                 dc_n_min: int | None = None,
                 faults=None, retry: RetryPolicy | None = None,
                 residual_check: bool = False, tracer=None):
        from repro.core import tuning
        if config is None:
            config = tuning.PipelineConfig.resolve(backend=backend)
            # Buckets re-resolve "auto" against their own dtype: float64 on
            # a TPU takes the ref backend (Pallas has no float64).
            self._backend = backend
        else:
            self._backend = config.backend
        if max_batch is not None:
            config = dataclasses.replace(config, max_batch=max_batch)
        self.config = config
        self.autotune = autotune
        self.autotune_cache = autotune_cache
        self.fused_n_max = fused_n_max           # fused-tier crossover, §13
        self.dc_n_min = dc_n_min                 # stage-3 D&C crossover, §14
        self.mesh = mesh                         # multi-device dispatch, §12
        self.faults = faults                     # fault injection hook, §15
        self.retry = retry if retry is not None else RetryPolicy()
        self.residual_check = bool(residual_check)
        self.quarantine = BucketQuarantine(
            threshold=self.retry.quarantine_threshold,
            cooldown_s=self.retry.quarantine_cooldown_s)
        self.buckets: dict[tuple, list[SVDRequest]] = {}
        self.finished: list[SVDRequest] = []
        self.calls = 0                           # batched pipeline invocations
        self.metrics = ServeMetrics()
        self.tracer = tracer                     # obs.Tracer or None, §16
        self._cfg_memo: dict[tuple, object] = {}  # bucket key -> resolved cfg
        self._degraded_memo: dict[tuple, object] = {}  # key -> ref-tier cfg

    def _span(self, name: str, **attrs):
        """A program span, recorded into the engine's tracer if it has one,
        else into the ambient tracer, if any (DESIGN.md §16)."""
        if self.tracer is None:
            return obs.span(name, **attrs)
        return self.tracer.span(name, **attrs)

    def submit(self, req: SVDRequest) -> None:
        assert req.matrix.ndim == 2 and req.matrix.shape[0] == req.matrix.shape[1]
        if req.arrived is None:
            req.arrived = time.monotonic()       # queue-age/latency clock, §16
        key = req.key()
        self.metrics.add(submitted=1,
                         bucket_hits=int(key in self._cfg_memo
                                         or key in self.buckets))
        self.buckets.setdefault(key, []).append(req)
        self.metrics.set_queue_depth(self.pending())

    def pending(self) -> int:
        return sum(len(v) for v in self.buckets.values())

    def _fused_n_max_for(self, key: tuple) -> int:
        """The fused-tier crossover governing this bucket (DESIGN.md §13).

        Precedence: an explicit engine ``fused_n_max`` pins it (0 disables
        the tier entirely); otherwise ``autotune=True`` consults the
        MEASURED crossover persisted by ``python -m repro.autotune
        --fused-crossover`` (bw-specific entry first, then the device-wide
        one); otherwise the static default
        ``tuning.DEFAULT_FUSED_CROSSOVER`` — the paper's small-n regime.
        """
        if self.fused_n_max is not None:
            return int(self.fused_n_max)
        _n, bw, dtype, _banded, compute_uv = key
        if self.autotune:
            from repro.autotune import cache as at_cache
            from repro.autotune import model as at_model
            tuned = at_cache.lookup_crossover(
                device_kind=at_model.device_kind(),
                dtype=np.dtype(dtype).name, compute_uv=compute_uv, bw=bw,
                path=self.autotune_cache)
            if tuned is not None:
                return tuned
        from repro.core import tuning
        return tuning.DEFAULT_FUSED_CROSSOVER

    def _dc_n_min_for(self, key: tuple) -> int:
        """The stage-3 D&C crossover governing this bucket (DESIGN.md §14).

        Precedence mirrors ``_fused_n_max_for``: an explicit engine
        ``dc_n_min`` pins it (0 disables the D&C tier); otherwise
        ``autotune=True`` consults the MEASURED crossover persisted by
        ``python -m repro.autotune --stage3-crossover``; otherwise the
        static default ``core.bidiag_dc.DEFAULT_DC_N_MIN``.
        """
        if self.dc_n_min is not None:
            return int(self.dc_n_min)
        _n, _bw, dtype, _banded, compute_uv = key
        if self.autotune:
            from repro.autotune import cache as at_cache
            from repro.autotune import model as at_model
            tuned = at_cache.lookup_stage3(
                device_kind=at_model.device_kind(),
                dtype=np.dtype(dtype).name, compute_uv=compute_uv,
                path=self.autotune_cache)
            if tuned is not None:
                return tuned
        from repro.core import bidiag_dc
        return bidiag_dc.DEFAULT_DC_N_MIN

    def _cfg_for(self, key: tuple):
        from repro.core import tuning
        if key in self._cfg_memo:
            return self._cfg_memo[key]
        n, bw, dtype, _banded, compute_uv = key
        entry = None
        if self.autotune:
            from repro.autotune import cache as at_cache
            from repro.autotune import model as at_model
            entry = at_cache.lookup(
                device_kind=at_model.device_kind(), n=n, bw=bw,
                dtype=np.dtype(dtype).name, compute_uv=compute_uv,
                backend=self.config.backend, path=self.autotune_cache)
        if entry is not None:
            # Tuned bucket: the measured optimum decides tw (and
            # max_batch when the search explored the batch axis — absent
            # otherwise, leaving the Eq.-1 default in charge).  The engine
            # max_batch remains a cap.
            eff = min(self.config.max_batch,
                      entry.get("max_batch")
                      or tuning.default_bucket_batch(n, bw))
            tw = entry["tw"]
        else:
            # Cache miss (or autotune off): the engine's own resolved
            # config stays in charge — an explicitly-configured tw is
            # never silently discarded.  The engine's max_batch is a CAP;
            # per bucket it is tightened by the Eq.-1 occupancy default so
            # large matrices (whose own wavefront already saturates the
            # chip) are not zero-padded 8x for nothing.
            eff = min(self.config.max_batch,
                      tuning.default_bucket_batch(n, bw))
            tw = self.config.tw

        # Stage-3 policy (§14): "auto" + the bucket's crossover collapses to
        # a concrete solver inside resolve (n is known here); dc_n_min < 1
        # means "D&C disabled" — pin bisection outright.
        dmin = self._dc_n_min_for(key)
        stage3 = "bisect" if dmin < 1 else "auto"

        def resolve(backend: str):
            return tuning.PipelineConfig.resolve(
                bw=bw, tw=tw, backend=backend,
                interpret=self.config.interpret, dtype=np.dtype(dtype), n=n,
                max_batch=max(1, eff), compute_uv=compute_uv, stage3=stage3,
                dc_leaf_n=self.config.dc_leaf_n, dc_n_min=max(dmin, 1))

        cfg = None
        if n <= self._fused_n_max_for(key):
            # Fused small-n tier (DESIGN.md §13): the whole per-matrix
            # pipeline as one dispatch.  A VMEM-infeasible n falls back to
            # the staged pipeline instead of failing the bucket.
            try:
                cfg = resolve("fused_small")
            except ValueError:
                cfg = None
        if cfg is None:
            cfg = resolve(self._backend)
        self.metrics.set_bucket_tier(key, self._tier_of(cfg, n), n=n,
                                     backend=cfg.backend)
        self._cfg_memo[key] = cfg
        return cfg

    @staticmethod
    def _tier_of(cfg, n: int) -> str:
        """Metrics attribution label for a resolved bucket config:
        "fused" (§13 one-dispatch tier), "staged-dc" (staged pipeline with
        the §14 D&C stage 3), or "staged" (bisection stage 3)."""
        if cfg.backend == "fused_small":
            return "fused"
        return "staged-dc" if cfg.stage3_for(n) == "dc" else "staged"

    def _pop(self, key: tuple, cap: int) -> list[SVDRequest]:
        """Dequeue up to ``cap`` requests of one bucket, submission order."""
        reqs = self.buckets[key][:cap]
        self.buckets[key] = self.buckets[key][cap:]
        if not self.buckets[key]:
            del self.buckets[key]
        self.metrics.set_queue_depth(self.pending())
        return reqs

    def _finish(self, req: SVDRequest, error: Exception | None = None, *,
                tier: str | None = None) -> None:
        """Complete one request exactly once: results already on it, or
        ``error``; resolve its future (async callers) either way.

        Deadline semantics are re-checked HERE, not only at admission: a
        request admitted in time but completed after its deadline is a
        timeout to the caller (nobody is waiting anymore) and counts in
        ``timed_out`` — its results stay on the request object for
        observability (the future resolves with :class:`TimeoutError`,
        ``req.sigma`` keeps the late answer).

        Successful completions feed the per-tier and per-bucket latency
        histograms (DESIGN.md §16) with the CLIENT-view latency
        (``submit`` -> completion); ``tier`` attributes it (falling back
        to the bucket's resolved tier when the caller doesn't know)."""
        if (error is None and req.deadline is not None
                and time.monotonic() > req.deadline):
            error = TimeoutError(
                f"request {req.uid} completed after its deadline "
                f"({time.monotonic() - req.deadline:.3f}s late); late "
                f"results remain on the request")
        req.error = error
        req.done = True
        self.finished.append(req)
        if error is None:
            self.metrics.add(completed=1)
            if req.arrived is not None:
                key = req.key()
                self.metrics.observe_latency(
                    tier or self.metrics.tier_of_bucket(key), key,
                    time.monotonic() - req.arrived)
        elif isinstance(error, TimeoutError):
            self.metrics.add(timed_out=1)        # serving failure, not pipeline
        else:
            self.metrics.add(failed=1)
        if req.future is not None:
            try:
                if error is not None:
                    req.future.set_exception(error)
                else:
                    req.future.set_result(req)
            except Exception:                    # noqa: BLE001 — caller
                pass                             # cancelled; result stays on req

    def _pipeline_call(self, key: tuple, cfg, mats: list[np.ndarray], *,
                       tier: str | None = None, inject: bool = True):
        """ONE batched pipeline dispatch for ``mats`` (padded to the bucket
        capacity): returns np ``(sigma, u, vt)`` sliced to ``len(mats)``
        (``u``/``vt`` None for values-only buckets).  Routes through the
        mesh (``core.distributed``) when the engine owns one.

        Fault-tolerance plumbing (DESIGN.md §15): when the engine owns a
        :class:`~repro.serve.faults.FaultPlan` and ``inject`` is True
        (primary path only — degraded dispatches pass ``inject=False``),
        the plan may delay/raise before dispatch and corrupt the sigma
        block after it.  Every result — injected or not — then passes the
        numerical-health guard, raising ``NumericalFault`` on garbage.

        Spans (DESIGN.md §16): ``serve/dispatch`` with the children
        ``serve/pad``, ``serve/pipeline`` (under which the pipeline's own
        spans nest), ``serve/copy_back`` and ``serve/validate``.  The
        engine's ``tracer``, if any, is activated for the call, so the
        pipeline's spans are recorded into it too; it changes nothing
        that runs."""
        with obs.activated(self.tracer), obs.span(
                "serve/dispatch", bucket=bucket_key_str(key),
                tier=tier or self._tier_of(cfg, key[0]), n=key[0],
                batch=len(mats), backend=cfg.backend, inject=inject):
            return self._pipeline_call_inner(key, cfg, mats, tier=tier,
                                             inject=inject)

    def _pipeline_call_inner(self, key: tuple, cfg, mats: list[np.ndarray],
                             *, tier: str | None, inject: bool):
        from repro.core import svd as svdmod
        n, _bw, dtype, banded, compute_uv = key
        faults = self.faults if inject else None
        if faults is not None:
            faults.before_dispatch(key)          # may sleep and/or raise
        with obs.span("serve/pad"):
            batch = np.zeros((cfg.max_batch, n, n), dtype)   # pad: zero matrices
            for i, m in enumerate(mats):
                batch[i] = m
            stacked = jnp.asarray(batch)
        if stacked.dtype != np.dtype(dtype):
            # jax_enable_x64 is off: fp64 requests are silently downcast by
            # jnp.asarray — serve at the effective precision instead of
            # tripping the config/input dtype-conflict check.
            cfg = dataclasses.replace(cfg, dtype=jnp.dtype(stacked.dtype).name)
        u = vt = None
        with obs.span("serve/pipeline"):
            if self.mesh is not None:
                from repro.core import distributed
                out = distributed.sharded_pipeline_dispatch(
                    stacked, self.mesh, config=cfg, banded=banded,
                    compute_uv=compute_uv, faults=faults,
                    on_shard_retry=lambda k_: self.metrics.add(
                        sharded_retries=k_))
                if compute_uv:
                    u, sig, vt = out
                else:
                    sig = out
                self.metrics.add(sharded_batches=1)
            elif compute_uv:
                fn = svdmod.banded_svd if banded else svdmod.svd
                u, sig, vt = fn(stacked, config=cfg, compute_uv=True)
            elif banded:
                sig = svdmod.banded_singular_values(stacked, bw=cfg.bw,
                                                    config=cfg)
            else:
                sig = svdmod.svd_batched(stacked, config=cfg)
        self.calls += 1
        self.metrics.add(batches=1, served_slots=len(mats),
                         padded_slots=cfg.max_batch - len(mats))
        self.metrics.add_tier(
            tier or self._tier_of(cfg, n), batches=1, served_slots=len(mats),
            padded_slots=cfg.max_batch - len(mats))
        k = len(mats)
        with obs.span("serve/copy_back"):
            sig = np.asarray(sig)[:k]
            if compute_uv:
                u, vt = np.asarray(u)[:k], np.asarray(vt)[:k]
        if faults is not None:
            sig = faults.corrupt_sigma(sig)
        # Numerical-health guard (§15): a NaN/Inf/garbage sigma must raise
        # NumericalFault here — never reach a caller as a silent answer.
        with obs.span("serve/validate"):
            svdmod.validate_sigma(sig)
            if compute_uv:
                svdmod.validate_uv(u, vt)
                if self.residual_check:
                    svdmod.spot_check_svd(batch[:k], u, sig, vt)
        return sig, u, vt

    # ------------------------------------------------------------------
    # fault-tolerant dispatch (DESIGN.md §15)
    # ------------------------------------------------------------------

    def _degraded_cfg(self, key: tuple):
        """The degraded-tier config for a bucket: same shapes, trusted
        ``ref`` backend, bisection stage 3 (the oracle solver).  Memoized
        per key — one resolution and one compile ever, like the primary."""
        from repro.core import tuning
        if key not in self._degraded_memo:
            n, bw, dtype, _banded, compute_uv = key
            self._degraded_memo[key] = tuning.PipelineConfig.resolve(
                bw=bw, backend="ref", dtype=np.dtype(dtype), n=n,
                max_batch=self.config.max_batch, compute_uv=compute_uv,
                stage3="bisect")
        return self._degraded_memo[key]

    def _note_failure(self, key: tuple, exc: Exception) -> None:
        """Record one primary-path failure: last-error attribution plus
        the circuit breaker's consecutive-failure count."""
        self.metrics.set_bucket_error(key, exc)
        if self.quarantine.record_failure(key):
            self.metrics.add(quarantined=1)
            self.metrics.set_bucket_quarantined(key, True)

    def _note_success(self, key: tuple) -> None:
        if self.quarantine.record_success(key):
            self.metrics.set_bucket_quarantined(key, False)

    def _deliver(self, key: tuple, reqs: list[SVDRequest], sig, u, vt,
                 tier: str | None = None) -> None:
        """Copy one dispatch's results onto its requests and complete them
        in submission (FIFO) order."""
        _n, _bw, _dtype, _banded, compute_uv = key
        for i, r in enumerate(reqs):
            r.sigma = sig[i]
            if compute_uv:
                r.u, r.vt = u[i], vt[i]
            self._finish(r, tier=tier)

    def _serve_degraded(self, key: tuple, reqs: list[SVDRequest],
                        cause: Exception | None) -> int:
        """Serve ``reqs`` on the degraded ref tier (quarantined bucket, or
        a request whose primary-path retries are exhausted).  The degraded
        dispatch is never fault-injected and still passes the numerical
        guard; if even the ref tier fails, the request finally surfaces
        ``cause`` (the primary-path error — more actionable than the
        fallback's own)."""
        with self._span("serve/degraded", bucket=bucket_key_str(key),
                        batch=len(reqs),
                        cause=repr(cause) if cause is not None else None):
            try:
                dcfg = self._degraded_cfg(key)
                sig, u, vt = self._pipeline_call(key, dcfg,
                                                 [r.matrix for r in reqs],
                                                 tier="degraded-ref",
                                                 inject=False)
            except Exception as exc:             # noqa: BLE001 — last resort
                for r in reqs:
                    self._finish(r, error=cause if cause is not None else exc)
                return len(reqs)
            self.metrics.add(degraded=len(reqs))
            self._deliver(key, reqs, sig, u, vt, tier="degraded-ref")
            return len(reqs)

    def _retry_request(self, key: tuple, cfg, req: SVDRequest,
                       exc: Exception) -> int:
        """The per-request retry ladder (DESIGN.md §15): after a failed
        primary attempt, retry with capped exponential backoff up to the
        policy's attempt bound (tighter for ``NumericalFault``), never
        sleeping past the request's deadline; on exhaustion fall through
        to the degraded ref tier."""
        policy = self.retry
        failures = 1
        self._note_failure(key, exc)
        while failures < policy.attempts_for(exc):
            delay = policy.backoff_for(failures, deadline=req.deadline,
                                       now=time.monotonic())
            if delay is None:                    # would sleep past deadline
                break
            if delay > 0:
                time.sleep(delay)
            if self.quarantine.active(key):      # tripped meanwhile
                break
            self.metrics.add(retried=1)
            try:
                with self._span("serve/retry", bucket=bucket_key_str(key),
                                attempt=failures, backoff_s=delay):
                    sig, u, vt = self._pipeline_call(key, cfg, [req.matrix])
            except Exception as exc2:            # noqa: BLE001 — ladder
                exc = exc2
                failures += 1
                self._note_failure(key, exc)
                continue
            self._note_success(key)
            self._deliver(key, [req], sig, u, vt)
            return 1
        return self._serve_degraded(key, [req], cause=exc)

    def _serve_batch(self, key: tuple, cfg, reqs: list[SVDRequest]) -> int:
        """Serve one dequeued batch; every request in ``reqs`` COMPLETES, in
        submission (FIFO) order — a failure is surfaced on the request
        (``req.error``) rather than raised out of the step.  A batch-level
        failure falls back to per-request dispatches (isolating poison
        requests), each of which enters the retry/backoff/degrade ladder
        (§15); a quarantined bucket skips the primary path entirely."""
        if self.quarantine.active(key):
            return self._serve_degraded(key, reqs, cause=None)
        try:
            sig, u, vt = self._pipeline_call(key, cfg,
                                             [r.matrix for r in reqs])
        except Exception as exc:                 # noqa: BLE001 — isolate below
            if len(reqs) == 1:
                return self._retry_request(key, cfg, reqs[0], exc)
            for r in reqs:                       # FIFO order preserved
                self._serve_batch(key, cfg, [r])
            return len(reqs)
        self._note_success(key)
        self._deliver(key, reqs, sig, u, vt)
        return len(reqs)

    def step(self) -> int:
        """Flush the fullest bucket with one batched call; #requests served.

        An empty engine is a no-op (returns 0, no dispatch).  Oversize
        buckets split at the bucket capacity: each step serves at most
        ``max_batch`` requests and leaves the tail queued, FIFO."""
        if not self.buckets:
            return 0
        key = max(self.buckets, key=lambda k: len(self.buckets[k]))
        try:
            cfg = self._cfg_for(key)
        except Exception as exc:                 # noqa: BLE001
            # The whole bucket shares the un-resolvable key (e.g. a
            # VMEM-infeasible (bw, tw)): fail its requests, keep serving
            # the other buckets.
            for r in self._pop(key, len(self.buckets[key])):
                self._finish(r, error=exc)
            return 0
        reqs = self._pop(key, cfg.max_batch)
        # Queue age is observed exactly once per request, here at dispatch
        # (the per-request fallback inside _serve_batch re-enters with the
        # same requests and must not re-observe).
        now = time.monotonic()
        for r in reqs:
            if r.arrived is not None:
                self.metrics.observe_queue_age(now - r.arrived)
        return self._serve_batch(key, cfg, reqs)

    def run(self, max_rounds: int = 10_000) -> list[SVDRequest]:
        rounds = 0
        while self.buckets and rounds < max_rounds:
            self.step()
            rounds += 1
        return self.finished
