"""Columnar compacting TimeSeriesStore: compaction invariants, batched
reads, and the FleetExecutor one-read_many-per-bin contract."""
import numpy as np
import pytest

from repro.core import Castor, ModelDeployment, Schedule
from repro.core.executor import FleetExecutor, LocalPoolExecutor
from repro.forecast import LinearForecaster
from repro.timeseries.store import TimeSeriesStore
from repro.timeseries.transforms import DAY, HOUR


def _reference(batches, start=None, end=None):
    """The seed store's semantics: concat everything, stable sort, slice."""
    t = np.concatenate([np.asarray(b[0], np.float64).ravel() for b in batches])
    v = np.concatenate([np.asarray(b[1], np.float64).ravel() for b in batches])
    order = np.argsort(t, kind="stable")
    t, v = t[order], v[order]
    lo = np.searchsorted(t, start) if start is not None else 0
    hi = np.searchsorted(t, end) if end is not None else t.size
    return t[lo:hi], v[lo:hi]


def _check_invariants(store, ts_id):
    s = store._data[ts_id]
    n_seg = sum(seg.n for seg in s.segments)
    assert n_seg + s.tail_n == s.count          # nothing lost or duplicated
    for seg in s.segments:
        assert np.all(np.diff(seg.times) >= 0)  # each segment sorted
        assert not seg.times.flags.writeable    # immutable columnar runs
        assert not seg.values.flags.writeable


# ---------------- ordering semantics ----------------
def test_out_of_order_appends_sorted_reads():
    st = TimeSeriesStore(tail_max=8)
    batches = [([5.0, 1.0, 9.0], [50, 10, 90]),
               ([3.0, 7.0], [30, 70]),
               ([0.5, 6.5, 2.5, 8.5], [5, 65, 25, 85])]
    for t, v in batches:
        st.append("x", t, v)
    rt, rv = st.read("x")
    et, ev = _reference(batches)
    np.testing.assert_array_equal(rt, et)
    np.testing.assert_array_equal(rv, ev)
    _check_invariants(st, "x")


def test_duplicate_timestamps_preserve_append_order():
    st = TimeSeriesStore(tail_max=2)   # force compactions between appends
    st.append("x", [5.0, 5.0], [1, 2])
    st.append("x", [5.0, 3.0], [3, 30])
    st.append("x", [5.0], [4])
    t, v = st.read("x")
    np.testing.assert_array_equal(t, [3.0, 5.0, 5.0, 5.0, 5.0])
    np.testing.assert_array_equal(v, [30, 1, 2, 3, 4])   # stable across merges


def test_range_read_half_open():
    st = TimeSeriesStore()
    st.append("x", [3.0, 1.0, 2.0], [30, 10, 20])
    t, v = st.read("x", 1.5, 3.0)                        # [start, end)
    assert list(t) == [2.0] and list(v) == [20]
    t, v = st.read("x", 1.0, 3.0)                        # start inclusive
    assert list(t) == [1.0, 2.0]


def test_read_straddles_compacted_and_tail():
    """Windows spanning sorted segments AND the unsorted tail are exact."""
    rng = np.random.default_rng(0)
    st = TimeSeriesStore(tail_max=16)
    batches = []
    for _ in range(20):                 # 200 points, many compactions
        t = rng.uniform(0, 1000, 10)
        v = rng.normal(size=10)
        batches.append((t, v))
        st.append("x", t, v)
    assert st._data["x"].segments       # some data compacted
    # last small batch stays in the tail
    t = rng.uniform(0, 1000, 3)
    v = rng.normal(size=3)
    batches.append((t, v))
    st.append("x", t, v)
    for start, end in [(None, None), (0.0, 500.0), (250.0, 750.0),
                       (999.0, 1001.0), (-5.0, 0.0)]:
        rt, rv = st.read("x", start, end)
        et, ev = _reference(batches, start, end)
        np.testing.assert_array_equal(rt, et)
        np.testing.assert_array_equal(rv, ev)
    _check_invariants(st, "x")


def test_randomized_interleaved_append_read_matches_reference():
    rng = np.random.default_rng(7)
    st = TimeSeriesStore(tail_max=32)
    batches = []
    for i in range(60):
        n = int(rng.integers(1, 40))
        t = rng.uniform(0, 1e4, n)
        dup = rng.random(n) < 0.2
        t[dup] = np.round(t[dup])               # inject duplicate timestamps
        v = rng.normal(size=n)
        batches.append((t, v))
        st.append("x", t, v)
        if i % 7 == 0:
            lo = float(rng.uniform(0, 1e4))
            hi = lo + float(rng.uniform(0, 5e3))
            rt, rv = st.read("x", lo, hi)
            et, ev = _reference(batches, lo, hi)
            np.testing.assert_array_equal(rt, et)
            np.testing.assert_array_equal(rv, ev)
    assert st.length("x") == sum(len(b[0]) for b in batches)


# ---------------- the sorted tail ----------------
#: chunk kinds of the oracle test; "late" ones take the out-of-order merge
IN_ORDER = ("single", "multi", "equal", "empty")
LATE = ("unsorted", "behind_tail", "behind_segment")


def _chunk(rng, kind, newest):
    """A chunk of ``kind`` against the newest time stored so far, on a
    half-hour grid so equal timestamps are common."""
    n = int(rng.integers(2, 7))
    step = rng.integers(0, 3, n) * 0.5
    if kind == "single":
        return np.array([newest + 0.5 * int(rng.integers(0, 3))])
    if kind == "multi":
        return newest + np.cumsum(step)
    if kind == "equal":
        return np.full(n, newest)
    if kind == "empty":
        return np.empty(0)
    if kind == "unsorted":
        return rng.permutation(newest + np.cumsum(step + 0.5))
    if kind == "behind_tail":               # just behind the newest points
        return np.sort(newest - rng.integers(0, 4, n) * 0.5)
    return rng.integers(0, 2 * max(int(newest), 1), n) * 0.5   # anywhere


@pytest.mark.parametrize("tail_max", [3, 16])
@pytest.mark.parametrize("mix", ["in_order", "late"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sorted_tail_reads_match_a_stable_sort_of_the_history(
        seed, mix, tail_max):
    """Random append sequences through one store: after every append each
    read form returns, bit for bit, the stable sort of the whole append
    history sliced, read-only; arrays handed out earlier never change."""
    rng = np.random.default_rng([seed, tail_max])
    kinds = IN_ORDER + (LATE if mix == "late" else ())
    st = TimeSeriesStore(tail_max=tail_max)
    hist_t, hist_v, handed = [], [], []
    newest = 10.0
    for step in range(80):
        t = _chunk(rng, kinds[int(rng.integers(len(kinds)))], newest)
        v = step * 100.0 + np.arange(t.size)     # unique: shows tie order
        st.append("x", t, v)
        hist_t.append(t)
        hist_v.append(v)
        newest = max(newest, float(t.max(initial=newest)))
        ot = np.concatenate(hist_t)
        order = np.argsort(ot, kind="stable")
        ot, ov = ot[order], np.concatenate(hist_v)[order]
        if not ot.size:
            continue
        since = float(rng.choice([newest, newest - 0.5, ot[-1] + 1.0,
                                  rng.choice(ot), ot[0] - 1.0]))
        lo, hi = sorted(float(x) for x in rng.choice(ot, 2))
        k = np.searchsorted(ot, since)
        a, b = np.searchsorted(ot, lo), np.searchsorted(ot, hi)
        # delta reads first: they never consolidate, so they meet tails
        # beside several segments
        (pair, _), prior = st.read_many(["x", "missing"], since=since,
                                        prior_counts=True)
        assert prior.tolist() == [k, 0]
        sizes, ft, fv = st.read_many_flat(["missing", "x"], since=since)
        assert sizes.tolist() == [0, ot.size - k]
        got = [pair, (ft, fv), st.read_many(["x"], lo, hi)[0],
               st.read("x", lo, hi), st.read("x")]
        want = [(ot[k:], ov[k:])] * 2 + [(ot[a:b], ov[a:b])] * 2 + [(ot, ov)]
        for (gt, gv), (wt, wv) in zip(got, want):
            np.testing.assert_array_equal(gt, wt)
            np.testing.assert_array_equal(gv, wv)
            # the flat form concatenates: its arrays are the caller's own
            assert gt is ft or not (gt.flags.writeable or gv.flags.writeable)
        handed += [(gt, gt.copy()) for gt, _ in got] \
            + [(gv, gv.copy()) for _, gv in got]
        _check_invariants(st, "x")
        s = st._data["x"]
        assert np.all(np.diff(s.tail_t[:s.tail_n]) >= 0)
    st.append("x", [newest + 1.0], [-1.0])
    st.compact()                                 # a flush and merges
    assert st.compaction_count and st.merge_count
    for arr, copy in handed:
        np.testing.assert_array_equal(arr, copy)
    assert (st.tail_merges > 0) == (mix == "late")


def test_tail_merges_count_only_out_of_order_chunks():
    """Hourly in-order appends with delta reads in between sort nothing,
    and the reads' spans carry ``tail_points=0``. One chunk behind the tail
    merges the tail and its points once, and the next read's span counts
    them."""
    from repro.obs.trace import Tracer, set_tracer
    tr = Tracer(capacity=256)
    prev = set_tracer(tr)
    try:
        st = TimeSeriesStore(tail_max=64)
        ids = ["a", "b"]
        for i in ids:
            st.append(i, np.arange(0.0, 40 * HOUR, HOUR), np.ones(40))
            st.compact(i)
        for h in range(40, 50):
            for i in ids:
                st.append(i, [h * HOUR + 0.05 * HOUR], [float(h)])
            raw, prior = st.read_many(ids, since=h * HOUR,
                                      prior_counts=True)
            assert [t.size for t, _ in raw] == [1, 1]
            assert prior.tolist() == [h, h]
        assert st.tail_sort_points == 0 and st.stats()["tail_merges"] == 0
        reads = [s for s in tr.spans() if s.name == "store.read_many"]
        assert [s.args["tail_points"] for s in reads] == [0] * 10
        st.append("a", [45.5 * HOUR, 44.5 * HOUR], [0.0, 0.0])
        assert st.tail_sort_points == 10 + 2
        assert st.stats()["tail_merges"] == 1
        st.read_many_flat(ids, since=40 * HOUR)
        assert tr.spans()[-1].args["tail_points"] == 12
    finally:
        set_tracer(prev)



@pytest.mark.parametrize("flat", [False, True], ids=["pairs", "flat"])
def test_read_span_counts_tail_sorts_since_the_previous_traced_read(flat):
    """An untraced read leaves the merges it follows to the next traced
    read, which counts every series' merges once, whichever ids it reads."""
    from repro.obs.trace import Tracer, set_tracer
    tr = Tracer(capacity=64)
    prev = set_tracer(tr)
    try:
        st = TimeSeriesStore()
        read = st.read_many_flat if flat else st.read_many
        st.append("a", [5.0, 6.0], [0.0, 0.0])
        st.append("a", [1.0], [0.0])              # behind the tail: 3 moved
        tr.enabled = False
        read(["a"], since=0.0)
        tr.enabled = True
        st.append("b", [2.0, 1.0], [0.0, 0.0])    # unsorted: 2 moved
        read(["a"], since=0.0)
        read(["a"], since=0.0)
        reads = [s for s in tr.spans() if s.name == "store.read_many"]
        assert [s.args["tail_points"] for s in reads] == [5, 0]
        assert st.tail_sort_points == 5 and st.stats()["tail_merges"] == 2
    finally:
        set_tracer(prev)

# ---------------- O(1) metadata ----------------
def test_last_first_time_without_consolidation():
    st = TimeSeriesStore(tail_max=1 << 30)   # nothing ever compacts
    st.append("x", [5.0, 2.0], [1, 1])
    st.append("x", [9.0, 0.5], [1, 1])
    assert st.last_time("x") == 9.0
    assert st.first_time("x") == 0.5
    assert st._data["x"].segments == []      # answered from metadata alone
    assert st.last_time("missing") is None


# ---------------- batched reads ----------------
def test_read_many_matches_individual_reads_and_counts_one_call():
    rng = np.random.default_rng(1)
    st = TimeSeriesStore(tail_max=64)
    ids = [f"s{i}" for i in range(8)]
    for i, ts in enumerate(ids):
        n = 50 + 10 * i
        st.append(ts, rng.uniform(0, 100, n), rng.normal(size=n))
    singles = [st.read(ts, 10.0, 90.0) for ts in ids]
    before_rm, before_r = st.read_many_count, st.read_count
    batch = st.read_many(ids + ["unknown"], 10.0, 90.0)
    assert st.read_many_count == before_rm + 1
    assert st.read_count == before_r            # no hidden per-series reads
    for (et, ev), (bt, bv) in zip(singles, batch[:-1]):
        np.testing.assert_array_equal(et, bt)
        np.testing.assert_array_equal(ev, bv)
    assert batch[-1][0].size == 0                # unknown id -> empty


def test_read_window_batch_shapes_and_mask():
    st = TimeSeriesStore()
    st.append("a", [1.0, 2.0, 3.0], [10, 20, 30])
    st.append("b", [2.5], [25])
    times, values, mask = st.read_window_batch(["a", "b", "c"], 0.0, 10.0)
    assert times.shape == values.shape == mask.shape == (3, 3)
    np.testing.assert_array_equal(mask, [[True, True, True],
                                         [True, False, False],
                                         [False, False, False]])
    np.testing.assert_array_equal(values[0], [10, 20, 30])
    assert values[1, 0] == 25 and values[1, 1] == 0.0    # zero padding
    # all-empty window
    t2, v2, m2 = st.read_window_batch(["c"], 0.0, 10.0)
    assert t2.shape == (1, 0) and not m2.any()


# ---------------- persistence ----------------
def test_save_load_roundtrip(tmp_path):
    rng = np.random.default_rng(2)
    st = TimeSeriesStore(tail_max=16)
    st.append("a", rng.uniform(0, 100, 50), rng.normal(size=50))
    st.append("a", rng.uniform(0, 100, 7), rng.normal(size=7))  # tail data
    st.append("b::x", [0.5], [9])
    st.save(str(tmp_path))
    st2 = TimeSeriesStore.load(str(tmp_path))
    assert set(st2.ids()) == {"a", "b::x"}
    for ts in ("a", "b::x"):
        t1, v1 = st.read(ts)
        t2, v2 = st2.read(ts)
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(v1, v2)
        _check_invariants(st2, ts)


# ---------------- compaction machinery ----------------
def test_compaction_bounds_segments_and_conserves_points():
    rng = np.random.default_rng(3)
    st = TimeSeriesStore(tail_max=64)
    total = 0
    for _ in range(200):
        n = int(rng.integers(1, 50))
        st.append("x", rng.uniform(0, 1e6, n), rng.normal(size=n))
        total += n
        _check_invariants(st, "x")
    s = st._data["x"]
    assert len(s.segments) <= int(np.log2(max(total, 2))) + 2   # tiered bound
    assert st.compaction_count > 0 and st.merge_count > 0
    st.compact("x")
    assert len(s.segments) == 1 and s.tail_n == 0
    assert s.segments[0].n == total == st.length("x")
    assert np.all(np.diff(s.segments[0].times) >= 0)


def test_small_appends_between_reads_do_not_rewrite_history():
    """Steady interleaved append/read must NOT consolidate the full series
    on every read — dirty data below 1/8 of the series is served via an
    ephemeral window merge (amortized O(log n + k) reads)."""
    rng = np.random.default_rng(8)
    st = TimeSeriesStore(tail_max=1024)
    st.append("x", rng.uniform(0, 1e6, 20_000), rng.normal(size=20_000))
    st.read("x")                        # consolidates once
    merged0 = st.merged_points
    ref = [(st.read("x")[0].copy(), st.read("x")[1].copy())]
    for _ in range(50):
        t = rng.uniform(0, 1e6, 5)
        v = rng.normal(size=5)
        ref.append((t, v))
        st.append("x", t, v)
        rt, rv = st.read("x", 2e5, 3e5)
        et, ev = _reference(ref, 2e5, 3e5)
        np.testing.assert_array_equal(rt, et)   # exact despite no rewrite
        np.testing.assert_array_equal(rv, ev)
    assert st.merged_points == merged0          # 20k history never re-merged


def test_repeated_reads_do_not_recompact():
    st = TimeSeriesStore(tail_max=8)
    rng = np.random.default_rng(4)
    st.append("x", rng.uniform(0, 10, 100), rng.normal(size=100))
    st.read("x")
    merges = st.merge_count
    compactions = st.compaction_count
    for _ in range(10):
        st.read("x", 2.0, 8.0)
    assert st.merge_count == merges             # later reads are pure slices
    assert st.compaction_count == compactions


# ---------------- fleet executor contract ----------------
def _small_castor(n_entities=4):
    c = Castor()
    c.add_signal("ENERGY_LOAD", "kWh")
    rng = np.random.default_rng(5)
    t = np.arange(0.0, 30 * DAY, HOUR)
    for i in range(n_entities):
        c.add_entity(f"P{i}", "PROSUMER", lat=35.0, lon=33.0 + 0.01 * i)
        hod = (t % DAY) / HOUR
        load = 2 + np.sin(2 * np.pi * hod / 24) + rng.normal(0, 0.05, t.size)
        c.ingest(f"ts{i}", t, load)
        c.link(f"ts{i}", "ENERGY_LOAD", f"P{i}")
    return c


def test_fleet_executor_issues_one_read_many_per_bin():
    """Acceptance criterion: a FleetExecutor score bin fetches all its
    series with ONE store.read_many call and ZERO single read()s."""
    c = _small_castor(4)
    now = 28 * DAY
    c.publish("lr", "1.0", LinearForecaster)
    c.deploy_for_all(package="lr", signal="ENERGY_LOAD", name_prefix="m",
                     kind="PROSUMER", train=Schedule(now, 1e12),
                     score=Schedule(now, HOUR),
                     user_params={"train_window_days": 7})
    res = c.tick(now, executor="fleet")          # train + first score
    assert all(r.ok for r in res), [r.error for r in res]

    jobs = c.scheduler.poll(now + HOUR)          # one score bin of 4 jobs
    assert len(jobs) == 4 and len({j.bin_key for j in jobs}) == 1
    fx = FleetExecutor(c)
    rm0, r0 = c.store.read_many_count, c.store.read_count
    res = fx.run(jobs)
    assert all(r.ok for r in res), [r.error for r in res]
    assert c.store.read_many_count - rm0 == 1    # ONE batched fetch per bin
    assert c.store.read_count - r0 == 0          # no per-instance reads
    assert len(fx.last_bin_stats) == 1
    assert fx.last_bin_stats[0]["read_many_calls"] == 1
    assert fx.last_bin_stats[0]["single_reads"] == 0


def test_fleet_and_local_predictions_identical():
    """Observational equivalence: scoring the same trained version through
    either executor yields identical forecasts."""
    def run(executor):
        c = _small_castor(3)
        now = 28 * DAY
        c.publish("lr", "1.0", LinearForecaster)
        c.deploy_for_all(package="lr", signal="ENERGY_LOAD", name_prefix="m",
                         kind="PROSUMER", train=Schedule(now, 1e12),
                         score=Schedule(now, HOUR),
                         user_params={"train_window_days": 7})
        assert all(r.ok for r in c.tick(now, executor="fleet"))  # same train
        jobs = c.scheduler.poll(now + HOUR)
        ex = FleetExecutor(c) if executor == "fleet" \
            else LocalPoolExecutor(c, max_parallel=4)
        assert all(r.ok for r in ex.run(jobs))
        return {f"m-P{i}": c.predictions.history(f"m-P{i}")[-1]
                for i in range(3)}

    fleet = run("fleet")
    local = run("local")
    assert fleet.keys() == local.keys()
    for k in fleet:
        np.testing.assert_array_equal(fleet[k].times, local[k].times)
        np.testing.assert_allclose(fleet[k].values, local[k].values,
                                   rtol=1e-5, atol=1e-6)


def test_empty_window_equivalent_across_executors():
    """An entity with no data in the train window gets the same outcome
    (zero-filled history, job ok) through both executors — one dead sensor
    must not poison a fleet bin nor diverge from the pool path."""
    def run(executor):
        c = _small_castor(2)
        now = 28 * DAY
        # dead sensor: linked series with data only far before the window
        c.add_entity("P_dead", "PROSUMER", lat=35.0, lon=34.0)
        c.ingest("ts_dead", [1.0, 2.0], [5.0, 5.0])
        c.link("ts_dead", "ENERGY_LOAD", "P_dead")
        c.publish("lr", "1.0", LinearForecaster)
        c.deploy_for_all(package="lr", signal="ENERGY_LOAD", name_prefix="m",
                         kind="PROSUMER", train=Schedule(now, 1e12),
                         score=Schedule(now, HOUR),
                         user_params={"train_window_days": 7})
        res = c.tick(now, executor=executor)
        return c, {(r.job.deployment_name, r.job.task): r.ok for r in res}

    cf, fleet = run("fleet")
    cl, local = run("local")
    assert fleet == local                       # identical per-job outcomes
    assert all(fleet.values()), fleet           # zero-fill semantics: jobs ok
    f = cf.predictions.history("m-P_dead")[-1]
    l = cl.predictions.history("m-P_dead")[-1]
    np.testing.assert_array_equal(f.times, l.times)
    np.testing.assert_allclose(f.values, l.values, rtol=1e-5, atol=1e-6)


def test_fleet_bins_split_by_execution_time():
    """Jobs from different polls carry different scheduled_at and a fleet
    score bin shares ONE execution time axis — scheduled_at is part of the
    bin key, so mixed-poll jobs execute as separate bins, each stamped at
    its own time (batching them would silently skew calendar features)."""
    c = _small_castor(2)
    now = 28 * DAY
    c.publish("lr", "1.0", LinearForecaster)
    c.deploy_for_all(package="lr", signal="ENERGY_LOAD", name_prefix="m",
                     kind="PROSUMER", train=Schedule(now, 1e12),
                     score=Schedule(now, HOUR),
                     user_params={"train_window_days": 7})
    assert all(r.ok for r in c.tick(now, executor="fleet"))
    mixed = c.scheduler.poll(now + HOUR) + c.scheduler.poll(now + 2 * HOUR)
    assert len({j.scheduled_at for j in mixed}) == 2
    fx = FleetExecutor(c)
    res = fx.run(mixed)
    assert all(r.ok for r in res), [r.error for r in res if not r.ok]
    assert len(fx.last_bin_stats) == 2          # one bin per poll time
    for i in range(2):
        created = [f.created_at for f in c.predictions.history(f"m-P{i}")]
        assert created == [now, now + HOUR, now + 2 * HOUR]


def test_fleet_score_mixed_now_instances_fail_loudly():
    """Model-layer backstop behind the bin split: calling fleet_score
    directly on instances with mixed execution times must refuse rather
    than silently compute wrong calendar features."""
    c = _small_castor(2)
    now = 28 * DAY
    up = {"train_window_days": 7, "now": now}
    insts = [LinearForecaster(
        context=c.graph.context("ENERGY_LOAD", f"P{i}"), task="score",
        model_id=f"x{i}", model_version=None,
        user_params={**up, "now": now + i * HOUR}, system=c)
        for i in range(2)]
    trained = LinearForecaster.fleet_train(insts)
    with pytest.raises(RuntimeError, match="mixes execution times"):
        LinearForecaster.fleet_score(insts, trained)


def test_castor_semantic_read_many():
    c = _small_castor(3)
    pairs = [("ENERGY_LOAD", f"P{i}") for i in range(3)]
    batch = c.read_many(pairs, 0.0, DAY)
    assert len(batch) == 3
    for i, (t, v) in enumerate(batch):
        et, ev = c.read("ENERGY_LOAD", f"P{i}", 0.0, DAY)
        np.testing.assert_array_equal(t, et)
        np.testing.assert_array_equal(v, ev)
