"""Observability layer tests (DESIGN.md §16): span semantics, the
profiler annotations every span opens with or without a tracer, the
``repro.*`` stage scopes in the compiled HLO of every entry point, the
program's compile counter, streaming histogram fidelity/merge/
serialization, JSONL trace round-trip, the Prometheus exposition endpoint,
that a tracer changes neither what compiles nor what is returned, the
serve dispatch's span tree, and the bounded-memory property of the
serve-tier latency histograms."""

import collections
import glob
import json
import re
import threading
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import svd as svdmod
from repro.core.tuning import PipelineConfig
from repro.obs import (JsonlExporter, MetricsServer, StreamingHistogram,
                       Tracer, load_jsonl, render_serve_metrics)
from repro.serve import ServeMetrics, SVDEngine, SVDRequest, bucket_key_str


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_attrs_and_timing():
    tr = Tracer("t")
    with tr.span("root", n=8) as root:
        with tr.span("child_a") as a:
            a.set(bw=4)
        with tr.span("child_b"):
            pass
    assert [r.name for r in tr.roots] == ["root"]
    assert [c.name for c in root.children] == ["child_a", "child_b"]
    assert root.attrs["n"] == 8
    assert root.children[0].attrs["bw"] == 4
    assert root.dur_s >= root.total_child_seconds() > 0.0
    assert root.find("child_b") == [root.children[1]]


def test_span_exception_safety():
    """An exception inside a span must close it (duration recorded, stack
    popped, error attribute set) and propagate unswallowed."""
    tr = Tracer("t")
    with pytest.raises(ValueError, match="boom"):
        with tr.span("outer"):
            with tr.span("inner"):
                raise ValueError("boom")
    (outer,) = tr.roots
    assert outer.dur_s is not None
    (inner,) = outer.children
    assert "boom" in inner.attrs["error"]
    assert "boom" in outer.attrs["error"]
    # the thread-local stack is clean: a new span becomes a fresh root
    with tr.span("after"):
        pass
    assert [r.name for r in tr.roots] == ["outer", "after"]


def test_ambient_tracer_and_null_span():
    """obs.span() is a no-op without an active tracer and records when one
    is activated; activation is scoped."""
    with obs.span("orphan") as sp:
        sp.set(x=1)                      # must not raise on the null span
    tr = Tracer("ambient")
    with obs.activated(tr):
        assert obs.current() is tr
        with obs.span("seen"):
            pass
    assert obs.current() is not tr
    assert [r.name for r in tr.roots] == ["seen"]


def test_spans_are_noop_under_jit_tracing():
    """Host spans inside jitted code must not fire at trace time."""
    tr = Tracer("t")

    @jax.jit
    def f(x):
        with obs.span("inside-jit"):
            return x * 2

    with obs.activated(tr):
        np.testing.assert_allclose(f(jnp.ones(3)), 2.0)
    assert tr.roots == []


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_one_bucket():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-4.0, sigma=1.2, size=5000)
    h = StreamingHistogram()
    h.extend(samples)
    r = h.bucket_width_ratio()
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q, method="higher"))
        approx = h.percentile(q)
        assert exact / r <= approx <= exact * r, (q, exact, approx)
    assert h.count == samples.size
    assert h.min == samples.min() and h.max == samples.max()
    np.testing.assert_allclose(h.mean, samples.mean())


def test_histogram_concurrent_merge_matches_numpy():
    """N threads each fill a private histogram; the merge must equal one
    histogram over all samples, and its percentiles must sit within one
    bucket width of numpy's exact ones."""
    rng = np.random.default_rng(1)
    chunks = [rng.lognormal(mean=-5.0, sigma=1.0, size=2000)
              for _ in range(4)]
    hists = [StreamingHistogram() for _ in chunks]

    def fill(h, vals):
        for v in vals:
            h.add(v)

    threads = [threading.Thread(target=fill, args=(h, c))
               for h, c in zip(hists, chunks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    merged = hists[0]
    for h in hists[1:]:
        merged.merge(h)
    allv = np.concatenate(chunks)
    assert merged.count == allv.size
    one = StreamingHistogram()
    one.extend(allv)
    np.testing.assert_array_equal(merged.counts(), one.counts())
    r = merged.bucket_width_ratio()
    for q in (50, 95, 99):
        exact = float(np.percentile(allv, q, method="higher"))
        assert exact / r <= merged.percentile(q) <= exact * r


def test_histogram_merge_scheme_mismatch_raises():
    with pytest.raises(ValueError, match="bucket schemes"):
        StreamingHistogram().merge(StreamingHistogram(buckets_per_decade=5))


def test_histogram_dict_roundtrip():
    h = StreamingHistogram()
    h.extend([1e-4, 3e-3, 3e-3, 0.2, 7.0])
    h2 = StreamingHistogram.from_dict(
        json.loads(json.dumps(h.to_dict())))
    np.testing.assert_array_equal(h.counts(), h2.counts())
    assert (h.count, h.sum, h.min, h.max) == (h2.count, h2.sum,
                                              h2.min, h2.max)
    for q in (50, 95, 99):
        assert h.percentile(q) == h2.percentile(q)


def test_histogram_bounded_memory_10k():
    """10k observations through the ServeMetrics latency surface must not
    grow any per-sample state: bucket arrays stay at their fixed size and
    the only O(N) quantity is the integer count."""
    m = ServeMetrics()
    key = (64, 8, "float64", False, False)
    m.set_bucket_tier(key, "staged", n=64, backend="ref")
    rng = np.random.default_rng(2)
    lats = rng.lognormal(mean=-5.0, sigma=0.8, size=10_000)
    for lat in lats:
        m.observe_latency("staged", key, float(lat))
        m.observe_queue_age(float(lat) / 4)
    hists = m.histograms()
    th = hists["tiers"]["staged"]
    bh = hists["buckets"][bucket_key_str(key)]
    for h in (th, bh, hists["queue_age"]):
        assert h.count == 10_000
        assert h.counts().size == h.num_buckets  # fixed, sample-independent
        assert h.num_buckets == StreamingHistogram().num_buckets
    r = th.bucket_width_ratio()
    for q in (50, 95, 99):
        exact = float(np.percentile(lats, q, method="higher"))
        assert exact / r <= th.percentile(q) <= exact * r
    snap = m.snapshot()
    assert snap["latency"]["tiers"]["staged"]["count"] == 10_000
    assert m.health()["latency_p99_ms"]["staged"] > 0


# ---------------------------------------------------------------------------
# JSONL export
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip(tmp_path):
    path = tmp_path / "trace.jsonl"
    tr = Tracer("t", jsonl=str(path))
    with tr.span("root", n=4) as root:
        with tr.span("leaf", stage=1):
            pass
    roots = load_jsonl(str(path))
    assert [r.name for r in roots] == ["root"]
    (rec,) = roots
    assert rec.attrs["n"] == 4
    (leaf,) = rec.children
    assert leaf.name == "leaf" and leaf.attrs["stage"] == 1
    assert rec.dur_s == pytest.approx(root.dur_s)
    assert rec.total_child_seconds() == pytest.approx(
        root.total_child_seconds())


def test_jsonl_exporter_threaded(tmp_path):
    path = tmp_path / "t.jsonl"
    tr = Tracer("t", jsonl=str(path))

    def work(i):
        with tr.span(f"w{i}"):
            with tr.span("inner"):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    roots = load_jsonl(str(path))
    assert sorted(r.name for r in roots) == [f"w{i}" for i in range(8)]
    assert all(len(r.children) == 1 for r in roots)


# ---------------------------------------------------------------------------
# compile counter
# ---------------------------------------------------------------------------

def test_fresh_shape_compile_counted_against_innermost_span(tmp_path):
    """A fresh shape's compile lands on the innermost open span (not its
    parent), in the process-wide counter, the JSONL export and the
    Prometheus rendering; a cached call compiles nothing."""
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones(37, jnp.float32)
    before = obs.compile_counts()["compiles"].get("inner_fresh", 0)
    tr = Tracer("c", jsonl=str(tmp_path / "c.jsonl"))
    with tr.span("outer_fresh") as outer:
        with tr.span("inner_fresh") as inner:
            f(x).block_until_ready()
        with tr.span("inner_cached") as cached:
            f(x).block_until_ready()
    tr.close()
    assert inner.compiles == 1
    assert outer.compiles == 0 and cached.compiles == 0
    assert obs.compile_counts()["compiles"]["inner_fresh"] == before + 1
    (rec,) = load_jsonl(str(tmp_path / "c.jsonl"))
    assert [c.compiles for c in rec.children] == [1, 0]
    assert re.search(r'^repro_compiles_total\{span="inner_fresh"\} \d+$',
                     obs.render_compile_metrics(), re.M)


# ---------------------------------------------------------------------------
# metrics endpoint
# ---------------------------------------------------------------------------

def test_metrics_server_scrape():
    m = ServeMetrics()
    m.add(submitted=3, completed=3, batches=1, served_slots=3)
    m.add_tier("fused", batches=1, served_slots=3, padded_slots=1)
    key = (16, 4, "float64", False, False)
    m.set_bucket_tier(key, "fused", n=16, backend="fused_small")
    for lat in (0.002, 0.004, 0.008):
        m.observe_latency("fused", key, lat)
        m.observe_queue_age(lat / 2)
    srv = MetricsServer(port=0)
    try:
        srv.register("svd", m)
        with urllib.request.urlopen(srv.url, timeout=10) as resp:
            assert resp.status == 200
            assert "version=0.0.4" in resp.headers["Content-Type"]
            text = resp.read().decode("utf-8")
    finally:
        srv.stop()
    assert 'repro_serve_requests_total{engine="svd",event="submitted"} 3' \
        in text
    assert 'tier="fused"' in text
    assert f'bucket="{bucket_key_str(key)}"' in text
    assert "repro_serve_queue_age_seconds_count" in text
    assert "repro_serve_health_status" in text
    # every sample line parses as `name{labels} value`, cumulative buckets
    # are monotone, and the +Inf bucket equals _count
    by_series = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        float(value)                     # parses
        assert name_part
        if "_bucket{" in name_part:
            # series identity = name + labels minus the `le` bound
            series = re.sub(r'le="[^"]*",?', "", name_part)
            by_series.setdefault(series, []).append(float(value))
    for series, counts in by_series.items():
        assert counts == sorted(counts), series
    assert ('repro_serve_latency_seconds_count{engine="svd",tier="fused"} 3'
            in text)
    assert "# TYPE repro_compiles_total counter" in text


def test_render_matches_histogram_counts():
    m = ServeMetrics()
    key = (8, 4, "float64", False, False)
    m.observe_latency("staged", key, 0.5)
    text = render_serve_metrics(m, engine="e2")
    assert 'repro_serve_latency_seconds_bucket{engine="e2",le="+Inf",' \
           'tier="staged"} 1' in text


# ---------------------------------------------------------------------------
# pipeline spans, scopes and compiles
# ---------------------------------------------------------------------------

def test_svd_batched_trace_coverage_and_compile_split():
    """One traced svd_batched call records a root with its config child,
    counts the fused pipeline's compile on the root the first time and
    none the second, and returns bit-identical sigma to the untraced
    call: the tracer does not change the path."""
    cfg = PipelineConfig.resolve(n=26, bw=4, tw=3, backend="ref",
                                 dtype=np.float64)
    rng = np.random.default_rng(0)
    mats = jnp.asarray(rng.standard_normal((3, 26, 26)))

    tr = Tracer("svd")
    sig = np.asarray(svdmod.svd_batched(mats, config=cfg, trace=tr))
    ref = np.asarray(svdmod.svd_batched(mats, config=cfg))
    np.testing.assert_array_equal(sig, ref)
    sig2 = np.asarray(svdmod.svd_batched(mats, config=cfg, trace=tr))
    np.testing.assert_array_equal(sig2, ref)

    first, second = tr.roots
    # svd_batched delegates to singular_values, which opens the root span
    assert first.name == second.name == "singular_values"
    assert first.attrs["n"] == 26 and first.attrs["batch"] == 3
    assert [c.name for c in first.children] == ["config"]
    assert first.compiles >= 1          # the one _three_stage executable
    assert second.compiles == 0
    assert first.dur_s >= first.total_child_seconds() > 0.0


def test_svd_uv_trace_has_replay_children():
    cfg = PipelineConfig.resolve(n=16, bw=4, tw=3, backend="ref",
                                 dtype=np.float64)
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((16, 16)))
    tr = Tracer("uv")
    u, s, vt = svdmod.svd(a, config=cfg, compute_uv=True, trace=tr,
                          check=True)
    np.testing.assert_allclose(
        np.asarray(u) @ np.diag(np.asarray(s)) @ np.asarray(vt),
        np.asarray(a), atol=1e-8)
    (root,) = tr.roots
    names = [c.name for c in root.children]
    assert names == ["config", "stage1", "pack", "stage2", "extract",
                     "replay", "stage3", "compose", "validate"], names
    (replay,) = root.find("replay")
    assert replay.find("replay_stage1")


def test_tape_bytes_counter_and_replay_span():
    """``repro_tape_bytes_total`` counts each recorded tape's bytes from its
    static shapes where it is made, stage 1's and stage 2's apart, and the
    ``replay_chase`` span carries the bytes it replays and the replay path
    (float64 on the ref backend: streamed)."""
    from repro.core import bulge_chasing as bc
    cfg = PipelineConfig.resolve(n=16, bw=4, tw=3, backend="ref",
                                 dtype=np.float64)
    a = _band(16, 4)

    def counted():
        return collections.Counter(obs.tape_bytes())

    c0 = counted()
    _, _, tapes = bc.bidiagonalize(a, bw=4, tw=3, config=cfg, tape=True)
    stage2 = sum(t.nbytes for t in tapes)
    assert stage2 == sum(t.v.size * 8 + t.tau.size * 8 for t in tapes) > 0
    assert counted() - c0 == {"stage2": stage2}
    tr = Tracer("uv")
    c0 = counted()
    svdmod.banded_svd(a, config=cfg, trace=tr)
    assert counted() - c0 == {"stage2": stage2}
    (span,) = tr.roots[0].find("replay_chase")
    assert span.attrs["tape_bytes"] == stage2
    assert span.attrs["path"] == "streamed"
    c0 = counted()
    svdmod.svd(jnp.asarray(np.random.default_rng(3).standard_normal((16, 16))),
               config=cfg)
    grew = counted() - c0
    assert set(grew) == {"stage1", "stage2"} and grew["stage1"] > 0
    text = obs.render_compile_metrics()
    assert "# TYPE repro_tape_bytes_total counter" in text
    assert (f'repro_tape_bytes_total{{stage="stage2"}} '
            f'{obs.tape_bytes()["stage2"]}') in text


def _band(n, bw, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    shape = (n, n) if batch is None else (batch, n, n)
    a = np.triu(rng.standard_normal(shape))
    return jnp.asarray(a - np.triu(a, bw + 1))


_ENTRIES = {
    # entry point -> (call on (a, cfg), input is banded, batch, scopes)
    "singular_values": (lambda a, c: svdmod.singular_values(a, config=c),
                        False, None, {"stage1", "stage2", "stage3"}),
    "banded_singular_values": (
        lambda a, c: svdmod.banded_singular_values(a, config=c),
        True, None, {"stage2", "stage3"}),
    "svd_batched": (lambda a, c: svdmod.svd_batched(a, c), False, 2,
                    {"stage1", "stage2", "stage3"}),
    "svd": (lambda a, c: svdmod.svd(a, config=c), False, None,
            {"stage1", "stage2", "replay", "stage3", "compose"}),
    "banded_svd": (lambda a, c: svdmod.banded_svd(a, config=c), True, None,
                   {"stage2", "replay", "stage3", "compose"}),
    "fused_values": (lambda a, c: svdmod.singular_values(a, config=c),
                     False, None, {"fused"}),
    "fused_uv": (lambda a, c: svdmod.svd(a, config=c), False, None,
                 {"fused", "stage3", "compose"}),
}


def _entry_case(name, n=16):
    call, banded, batch, scopes = _ENTRIES[name]
    backend = "fused_small" if name.startswith("fused") else "ref"
    cfg = PipelineConfig.resolve(n=n, bw=4, tw=3, backend=backend,
                                 dtype=np.float64)
    a = _band(n, 4, batch=batch) if banded else jnp.asarray(
        np.random.default_rng(2).standard_normal(
            ((batch,) if batch else ()) + (n, n)))
    return call, cfg, a, scopes


@pytest.mark.parametrize("entry", sorted(_ENTRIES))
def test_stage_scopes_in_compiled_hlo(entry):
    """Each stage's jitted body is traced as a call named repro.<stage>,
    so the name is in the op_name of the compiled ops whatever entry point
    (and whatever enclosing jit) runs the stage, and in the module text
    without debug information, which the persistent compilation cache
    hashes: a build without the scopes cannot share its entries."""
    call, cfg, a, scopes = _entry_case(entry)
    lowered = jax.jit(lambda x: call(x, cfg)).lower(a)
    text = lowered.compile().as_text()
    found = set(re.findall(r'op_name="[^"]*?repro\.(\w+)', text))
    assert scopes <= found, (entry, found)
    keyed = set(re.findall(r"@repro\.(\w+)", lowered.as_text(debug_info=False)))
    assert scopes <= keyed, (entry, keyed)


@pytest.mark.parametrize("entry", ["banded_singular_values", "svd",
                                   "singular_values", "fused_uv"])
def test_tracer_adds_no_compiles_and_changes_no_values(entry):
    """The same call with and without an active Tracer compiles the same
    number of executables (the program's own counter, caches cleared
    before each) and returns identical values."""
    call, cfg, a, _ = _entry_case(entry, n=20)

    def total():
        return sum(obs.compile_counts()["compiles"].values())

    jax.clear_caches()
    c0 = total()
    plain = jax.tree.map(np.asarray, call(a, cfg))
    untraced = total() - c0
    jax.clear_caches()
    c0 = total()
    tr = Tracer("same")
    with obs.activated(tr):
        traced = jax.tree.map(np.asarray, call(a, cfg))
    assert total() - c0 == untraced > 0
    jax.tree.map(np.testing.assert_array_equal, traced, plain)
    (root,) = tr.roots
    assert root.find("config")


def test_annotations_on_host_plane_without_tracer(tmp_path):
    """With no Tracer active, a profiler capture still sees every span of
    the band path as a repro/<name> annotation on the host plane, with
    its attributes as stats."""
    assert obs.current() is None
    a = _band(16, 4)
    cfg = PipelineConfig.resolve(n=16, bw=4, tw=3, backend="ref",
                                 dtype=np.float64)
    svdmod.banded_singular_values(a, config=cfg).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        svdmod.banded_singular_values(a, config=cfg,
                                      check=True).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = jax.profiler.ProfileData.from_file(path)
    events = {ev.name: dict(ev.stats)
              for plane in prof.planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro/")}
    assert {"repro/banded_singular_values", "repro/config", "repro/pack",
            "repro/stage2", "repro/extract", "repro/stage3",
            "repro/validate"} <= set(events), sorted(events)
    assert events["repro/stage2"]["b_in"] == 4
    assert events["repro/banded_singular_values"]["n"] == 16


# ---------------------------------------------------------------------------
# serve-tier spans
# ---------------------------------------------------------------------------

def test_engine_dispatch_spans_and_latency_histograms():
    tr = Tracer("serve")
    eng = SVDEngine(backend="ref", tracer=tr)
    rng = np.random.default_rng(3)
    for i in range(4):
        eng.submit(SVDRequest(uid=i, matrix=rng.standard_normal((16, 16)),
                              bw=4))
    done = eng.run()
    assert all(r.error is None for r in done)
    names = [r.name for r in tr.roots]
    assert "serve/dispatch" in names
    disp = next(r for r in tr.roots if r.name == "serve/dispatch")
    assert disp.attrs["bucket"] == bucket_key_str(
        (16, 4, "float64", False, False))
    snap = eng.metrics.snapshot()
    assert sum(row["count"]
               for row in snap["latency"]["tiers"].values()) == 4
    assert snap["latency"]["queue_age"]["count"] == 4


def test_serve_spans_nest_under_dispatch():
    """Every serve span of one dispatch is a child of serve/dispatch, and
    the pipeline's own spans nest under serve/pipeline."""
    tr = Tracer("serve")
    eng = SVDEngine(backend="ref", tracer=tr)
    rng = np.random.default_rng(4)
    for i in range(3):
        eng.submit(SVDRequest(uid=i, matrix=rng.standard_normal((16, 16)),
                              bw=4))
    assert all(r.error is None for r in eng.run())
    (disp,) = [r for r in tr.roots if r.name == "serve/dispatch"]
    assert [c.name for c in disp.children] == [
        "serve/pad", "serve/pipeline", "serve/copy_back", "serve/validate"]
    (pipe,) = disp.find("serve/pipeline")
    assert [c.name for c in pipe.children] == ["singular_values"]
