"""Test harness: force an 8-virtual-device CPU mesh — the analog of the
reference's local[2] SparkSession test fixture (reference
utils/.../test/TestSparkContext.scala:36-79). Same code paths as a real TPU
slice, 8 host devices."""
import os

# set before the first ``import jax`` anywhere in the session: tests run on
# the CPU whatever hardware the machine has. The compile cache follows the
# package's own rule (utils/jax_cache.py): JAX_COMPILATION_CACHE_DIR when
# set, else the fixed in-checkout directory.
os.environ["JAX_PLATFORMS"] = "cpu"
# shrink DEFAULT selector grids so CPU suites stay fast (full-fidelity run:
# TG_FAST_GRIDS=0 pytest tests/); explicit grids in tests are unaffected
os.environ.setdefault("TG_FAST_GRIDS", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(42)


@pytest.fixture(autouse=True)
def _no_observability_leak():
    """Span buffers and metric registries are process-global (like the
    reference's one SparkListener per context): a test that enables
    tracing/metrics and records telemetry must not bleed spans, counters,
    or a forced-enabled state into later tests — cross-test metric bleed
    would make latency/counter assertions order-dependent. Mirrors the
    chaos-site no-leak check below: assert clean on entry, hard-reset on
    exit (fresh tracer + registry + env-driven enablement)."""
    from transmogrifai_tpu import observability
    from transmogrifai_tpu.observability import metrics as _om
    from transmogrifai_tpu.observability import trace as _ot

    assert not _ot.tracer().finished(), (
        "span buffer leaked from a previous test: "
        f"{[s.name for s in _ot.tracer().finished()][:10]}")
    assert not _om.registry().snapshot(), (
        "metrics registry leaked from a previous test: "
        f"{sorted(_om.registry().snapshot())}")
    yield
    observability.reset()


@pytest.fixture(autouse=True)
def _no_blackbox_leak():
    """The flight recorder is ALWAYS ON (TG_BLACKBOX; unlike TG_TRACE it
    has no opt-in), so every test records events — that is the feature,
    not a leak. What must not bleed between tests: recorder contents
    (cross-test event bleed would make timeline assertions
    order-dependent), a forced enable/disable override, the post-mortem
    rate-limit counters, and bundle files in the default
    TG_POSTMORTEM_DIR (trigger events fired by breaker/oom/drift tests
    dump real bundles there). Probes + cleanup live in
    robustness/oracles.py like the other leak checks; module-scoped
    fixtures may record during setup, so the recorder is cleared (not
    asserted empty) on entry."""
    from transmogrifai_tpu.observability import blackbox as _bb
    from transmogrifai_tpu.observability import postmortem as _pm
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.stray_postmortem_bundles(), (
        "post-mortem bundle(s) leaked from a previous test: "
        f"{oracles.stray_postmortem_bundles()}")
    assert not oracles.blackbox_violations(), (
        f"blackbox state leaked into this test: "
        f"{oracles.blackbox_violations()}")
    _bb.recorder().clear()
    yield
    oracles.clean_postmortem_bundles()
    _bb.reset()
    _pm.reset()


@pytest.fixture(autouse=True)
def _no_ledger_leak():
    """The compile ledger and device-memory observatory are process-global
    (one ledger per process, like the flight recorder) and record on every
    program build — that is the feature, not a leak. What must not bleed
    between tests: ledger records and per-identity classification memory
    (cross-test cause assertions would become order-dependent — a plan
    built by an earlier test would turn this test's cold build into a
    spurious cache-eviction), a forced TG_LEDGER override, observatory
    peaks, and cost-table rows (a stray row would leak into the next
    test's saved MANIFEST `costs` section). Module-scoped fixtures may
    build programs during setup, so the ledger is cleared (not asserted
    empty) on entry; the bound/override oracle runs both ways
    (robustness/oracles.py ``ledger_violations``)."""
    from transmogrifai_tpu.observability import devicemem as _dm
    from transmogrifai_tpu.observability import ledger as _lg
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.ledger_violations(), (
        f"compile-ledger state leaked into this test: "
        f"{oracles.ledger_violations()}")
    _lg.ledger().clear()
    _dm.observatory().clear()
    yield
    violations = oracles.ledger_violations()
    _lg.reset()
    _dm.reset()
    assert not violations, (
        f"a test leaked compile-ledger state: {violations}")


@pytest.fixture(autouse=True)
def _no_programstore_leak():
    """The AOT program store keeps process-global state: open read
    sessions (whose mere presence flips later ledger builds from `cold`
    to `aot-miss`), capture scopes, hit/miss accounting, and a possible
    forced TG_AOT override. A session opened by one test's
    ``registry.load`` bleeding into the next would make cause-
    classification assertions order-dependent, and a leaked capture
    scope would keep exporting every later test's traced programs into
    a dead tmp dir. Mirrors the ledger fixture: assert no
    capture/override on entry, hard-reset (sessions + stats included)
    on exit, and fail the test that leaked (robustness/oracles.py
    ``programstore_violations`` — also run by the campaign engine after
    every schedule)."""
    from transmogrifai_tpu.programstore import store as _ps
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.programstore_violations(), (
        f"AOT program-store state leaked into this test: "
        f"{oracles.programstore_violations()}")
    _ps.reset()
    yield
    leaks = oracles.programstore_violations()
    _ps.reset()
    assert not leaks, f"a test leaked AOT program-store state: {leaks}"


@pytest.fixture(autouse=True)
def _no_slo_leak():
    """The windowed time-series sampler and the SLO engine are
    process-global: attached sampler sources keep the shared
    ``tg-sampler`` thread alive and snapshot their registry forever, and
    a registered SLOSpec silently changes every later runtime's budgets
    and alert thresholds. Assert clean on entry; on exit force-detach
    sources, drop specs, retire the thread, and fail the test that
    leaked them. Probes + cleanup live in robustness/oracles.py (also
    run by the campaign engine after every schedule). Defined BEFORE the
    serving no-leak fixture so this teardown runs AFTER runtimes (which
    attach sources on start and detach on close) are force-closed."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.slo_violations(), (
        f"sampler/SLO state leaked into this test: "
        f"{oracles.slo_violations()}")
    yield
    leaks = oracles.slo_violations()
    oracles.clean_slo_state()
    from transmogrifai_tpu.observability import timeseries as _ts
    _ts.idle_join()
    assert not leaks, f"a test leaked sampler/SLO state: {leaks}"
    stray = oracles.leaked_threads(("tg-sampler",))
    assert not stray, f"sampler thread(s) survived a test: {stray}"


@pytest.fixture(autouse=True)
def _no_plan_cache_leak():
    """Compiled transform plans pin jitted executables (and the stage
    objects they closed over), so the LRU must be provably bounded and must
    not bleed plans — or a forced-enabled/disabled planner state — between
    tests: a stale plan keyed to dead stage objects would silently serve
    the wrong fitted constants if an id() were ever recycled. Assert clean
    + bounded on entry (the check itself is the shared plan-cache oracle —
    robustness/oracles.py, also run by the chaos-campaign engine after
    every schedule), hard-reset on exit."""
    from transmogrifai_tpu import plan as _plan
    from transmogrifai_tpu.robustness import oracles

    problems = oracles.plan_cache_violations()
    assert not problems, f"plan-cache state leaked into this test: {problems}"
    # module-scoped fixtures train models during setup (before this
    # function-scoped fixture runs), so the cache may hold their plans —
    # drop them so every TEST starts with an empty cache
    _plan.clear_plan_cache()
    yield
    _plan.clear_plan_cache()
    _plan.enable_planning(None)


@pytest.fixture(autouse=True)
def _no_mesh_sharding_leak():
    """Mesh/global-sharding state must not bleed across tests (mirrors the
    plan-cache and observability no-leak fixtures): an active ``with mesh:``
    context entered by one test would silently re-shard every later test's
    jitted programs, and a mesh-keyed fused sweep program left in the
    validator LRU pins a dead test mesh plus per-device buffers for the
    whole session. Assert no ambient mesh context on entry and exit;
    hard-drop mesh-keyed programs on exit (mesh tests recompile cheaply —
    CPU programs — and must not subsidize later tests)."""
    import jax
    from jax._src import mesh as _jmesh

    from transmogrifai_tpu.impl.tuning import validators as _validators

    def _ambient_mesh():
        # ``with mesh:`` lands in thread_resources; ``jax.set_mesh`` (what
        # histeng.engine_mesh enters) lands in the abstract-mesh context,
        # which is part of every jit cache key
        m = _jmesh.thread_resources.env.physical_mesh
        if not m.empty:
            return m
        m = jax.sharding.get_abstract_mesh()
        return None if m.empty else m

    assert _ambient_mesh() is None, (
        f"a mesh context leaked from a previous test: {_ambient_mesh()}")
    yield
    leaked = _ambient_mesh()
    _validators.clear_mesh_programs()
    assert leaked is None, f"a test leaked an active mesh context: {leaked}"


@pytest.fixture(autouse=True)
def _no_hist_engine_leak():
    """Histogram-engine state must not bleed across tests (mirrors the
    mesh no-leak fixture): a leaked ``engine_mesh`` context would
    silently pin the next test's single-device tree traces to a dead
    mesh's 'data' axis, and the contraction-factory cache must stay
    bounded. Assert clean on entry and exit via the `oracles` probe;
    clear the engine's own caches on exit."""
    from transmogrifai_tpu import histeng as _histeng
    from transmogrifai_tpu.robustness import oracles as _oracles

    assert _oracles.histeng_violations() == []
    yield
    leaks = _oracles.histeng_violations()
    _histeng.clear_engine_caches()
    assert leaks == [], f"histogram-engine state leaked: {leaks}"


@pytest.fixture(autouse=True)
def _no_serving_leak():
    """Serving runtimes own a batcher thread, a bounded queue, and breaker
    state — all process-visible. A test that leaks a running runtime would
    keep scoring (and writing metrics) underneath every later test, and a
    leaked tg-serve thread would pin its model alive for the session.
    Assert none are live on entry; on exit force-close leftovers and fail
    the test that leaked them (mirrors the observability/plan/mesh no-leak
    fixtures: assert clean entry, hard-reset exit)."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.leaked_serving_runtimes(), (
        "serving runtime(s) leaked from a previous test: "
        f"{oracles.leaked_serving_runtimes()}")
    yield
    leaked = oracles.close_leaked_serving()
    assert not leaked, (
        f"a test leaked running serving runtime(s): {leaked}")
    # "tg-serve" prefix-matches the batcher (tg-serve[<model>]) AND the
    # pipelined completer (tg-serve-completer[<model>]): a completer that
    # outlives its runtime fails the leaking test here
    stray = oracles.leaked_threads(("tg-serve",))
    assert not stray, f"serving thread(s) survived a test: {stray}"


@pytest.fixture(autouse=True)
def _no_placement_leak():
    """A fleet placer holds residency/LRU state plus single-flight
    page-in events — a leaked placer with an in-flight page-in would
    block every later waiter for that model, and a stale residency map
    would misroute later fleets sharing the name. Defined BEFORE the
    fleet fixture so this teardown runs AFTER the fleet sweep: closing
    a leaked front door closes its placer, and anything still live here
    was detached. Probes + cleanup live in robustness/oracles.py
    (``placement_violations``, also run by the campaign engine after
    every schedule)."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.placement_violations(), (
        "placer(s) leaked from a previous test: "
        f"{oracles.placement_violations()}")
    yield
    leaks = oracles.placement_violations()
    oracles.close_leaked_placers()
    assert not leaks, f"a test leaked live placer(s): {leaks}"


@pytest.fixture(autouse=True)
def _no_fleet_leak():
    """A fleet front door owns a probe thread plus N replica registries'
    worth of batcher threads — a leaked fleet keeps routing (and
    spawning/retiring replicas under autoscale) underneath every later
    test. Defined AFTER the serving fixture so this teardown runs
    FIRST: closing a leaked fleet closes its replicas' runtimes too,
    and the serving fixture then verifies nothing survived. Probes +
    cleanup live in robustness/oracles.py (also run by the campaign
    engine after every schedule)."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.leaked_fleets(), (
        "fleet front door(s) leaked from a previous test: "
        f"{oracles.leaked_fleets()}")
    yield
    leaked = oracles.close_leaked_fleets()
    assert not leaked, (
        f"a test leaked running fleet front door(s): {leaked}")
    stray = oracles.leaked_threads(("tg-fleet",))
    assert not stray, f"fleet thread(s) survived a test: {stray}"


@pytest.fixture(autouse=True)
def _no_net_leak():
    """A network edge owns a listening socket plus a ``tg-net`` thread
    running a private asyncio loop — a leaked edge keeps accepting
    connections (and holding its port) underneath every later test.
    Defined AFTER the fleet fixture so this teardown runs FIRST:
    closing a leaked edge resolves its in-flight connections (typed
    ``server_close`` sheds) while the fleet/runtime it fronts still
    accepts. Probes + cleanup live in robustness/oracles.py
    (``net_violations``, also run by the campaign engine after every
    schedule)."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.net_violations(), (
        "network edge(s) leaked from a previous test: "
        f"{oracles.net_violations()}")
    yield
    leaked = oracles.close_leaked_net_edges()
    assert not leaked, (
        f"a test leaked running network edge(s): {leaked}")
    stray = oracles.leaked_threads(("tg-net",))
    assert not stray, f"net edge thread(s) survived a test: {stray}"


@pytest.fixture(autouse=True)
def _no_drift_leak():
    """Drift refits run on background ``tg-drift-refit`` daemon threads
    (serving/registry.py) that retrain + save + hot-swap a model. A refit
    leaking out of a test would keep training (and writing model dirs +
    metrics) underneath later tests. Mirrors the serving no-leak fixture:
    assert none live on entry, join + fail on exit."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.leaked_drift_refits(), (
        "drift refit thread(s) leaked from a previous test: "
        f"{oracles.leaked_drift_refits()}")
    yield
    still = oracles.join_drift_refits(timeout=30)
    assert not still, (
        f"a test leaked running drift refit thread(s): {still}")


@pytest.fixture(autouse=True)
def _no_stream_leak():
    """The streaming input engine owns an ordered committer thread
    (``tg-stream-feed``), a pool of producer workers
    (``tg-stream-w<i>``), and up to prefetch+1 host/device-resident
    chunk buffers. A leaked feed would keep reading + uploading chunks
    (and counting transfer bytes into the metrics registry) underneath
    later tests; a leaked tg-stream thread — committer OR worker — pins
    its chunk source alive for the session. Mirrors the serving no-leak
    fixture: assert clean entry, force-close + fail on exit; the
    ``tg-stream`` prefix sweep covers the whole worker pool."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.leaked_stream_feeds(), (
        "stream feed(s) leaked from a previous test")
    yield
    leaked = oracles.close_leaked_feeds()
    assert not leaked, f"a test leaked {len(leaked)} open DeviceFeed(s)"
    # a producer the test unwedged a moment ago (after an abort had closed
    # the feed over it) is on its way out, not leaked: give it the time a
    # loaded machine needs to schedule it
    import time
    deadline = time.monotonic() + 5.0
    stray = oracles.leaked_threads(("tg-stream",))
    while stray and time.monotonic() < deadline:
        time.sleep(0.01)
        stray = oracles.leaked_threads(("tg-stream",))
    assert not stray, f"stream feed thread(s) survived a test: {stray}"


@pytest.fixture(autouse=True)
def _no_watchdog_leak():
    """Watchdog hearts drive a shared ``tg-watchdog`` scanner thread that
    lives exactly as long as hearts are registered (robustness/watchdog.py)
    — a heart leaked by a test (an unclosed runtime/feed, a wedged refit)
    would keep the scanner alive and could fire stalls into later tests'
    fault logs. Mirrors the serving/stream no-leak fixtures: assert no
    hearts on entry, close leftovers + join the scanner + fail on exit."""
    from transmogrifai_tpu.robustness import oracles

    assert not oracles.leaked_watchdog_hearts(), (
        "watchdog heart(s) leaked from a previous test: "
        f"{oracles.leaked_watchdog_hearts()}")
    yield
    leaked = oracles.close_leaked_hearts()
    assert not leaked, (
        f"a test leaked open watchdog heart(s): {leaked}")
    stray = oracles.leaked_threads(("tg-watchdog",))
    assert not stray, f"watchdog thread(s) survived a test: {stray}"


@pytest.fixture(autouse=True)
def _no_fault_injection_leak(request):
    """Fault-injection sites must be inert outside chaos tests: an armed
    site leaking out of a ``chaos``-marked test (or in via a stray
    TG_FAULTS env without TG_CHAOS) would poison unrelated tests' — and
    production paths' — behavior silently. Covers every registered site,
    the ``preempt.*`` preemption sites included — a leaked armed
    SimulatedPreemption would kill an unrelated test's train() mid-DAG —
    and the call counters, so a later chaos test never inherits a stale
    fire position."""
    import os as _os

    from transmogrifai_tpu.robustness import faults

    is_chaos = (request.node.get_closest_marker("chaos") is not None
                or bool(_os.environ.get(faults.CHAOS_ENV)))
    if not is_chaos:
        assert not faults.active_sites(), (
            "fault-injection sites are armed outside a chaos test: "
            f"{faults.active_sites()}")
        assert not faults._CALLS, (
            "fault-injection call counters leaked from a previous test: "
            f"{dict(faults._CALLS)}")
        assert not faults._FIRED, (
            "fired-injection counters leaked from a previous test: "
            f"{dict(faults._FIRED)}")
    yield
    if not is_chaos:
        assert not faults.active_sites(), (
            "a test leaked armed fault-injection sites: "
            f"{faults.active_sites()}")
    else:
        # belt and braces: a chaos test that failed before its injected()
        # context exited — or died at an injected preemption — must not
        # poison the rest of the session
        faults.clear()


@pytest.fixture(autouse=True)
def _no_campaign_leak(request):
    """Campaign-marked tests drive MANY arm/run/disarm cycles through the
    chaos-campaign engine (robustness/campaign.py) — hundreds of scenario
    runs per test, each spawning runtimes, feeds, and hearts. The engine
    checks the no-leak oracles after every schedule; this fixture is the
    backstop asserting the TEST as a whole left the process clean, via
    the same callable oracles the engine uses (robustness/oracles.py)."""
    yield
    if request.node.get_closest_marker("campaign") is not None:
        from transmogrifai_tpu.robustness import oracles
        leaks = oracles.campaign_violations()
        assert not leaks, f"campaign test leaked process state: {leaks}"
