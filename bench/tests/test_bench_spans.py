"""The program's spans in a run of each cell, the readers of the per-layer
metrics they feed, and ``host_spans``' split of device idle time by host
span: on synthetic events and on a recorded chip trace."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import host_spans as hs
import run as bench

ROOT = Path(__file__).resolve().parents[2]
CELLS = {"ann": "ann.cyprus531_score", "lr": "lr.hourly_score_4096"}
#: one per fleet bin, under ``exec.bin`` unless named otherwise
BIN_SPANS = {"exec.prepare": "exec.bin", "runtime.advance": "exec.bin",
             "score.rollout": "exec.bin", "device.wait": "score.rollout",
             "score.bands": "exec.bin", "store.write": "exec.bin"}
READERS = ("persist_ms.score", "prepare_ms.score", "device_wait_ms.score",
           "tail_sort_points.score", "idle_unattributed_ms.score")
#: two ANN ticks (8 prosumers, 4 x 512) traced on one TPU v5e
RECORDED = Path(__file__).parent / "data_spans"


@pytest.fixture(scope="module", params=sorted(CELLS))
def tiny_run(request):
    """A window of the cell at 6 prosumers (ANN 32 wide), on the CPU."""
    cell = bench.load_cell(ROOT, CELLS[request.param])
    cell["traffic"]["site"].update(n_prosumers=6, n_feeders=2)
    if request.param == "ann":
        cell["config"]["user_params"].update(hidden=32)
    run = bench.Run(cell, 2 ** 31 + 11, require_chip=False)
    run.setup()
    run.window(0.2)
    return run


def test_each_bin_opens_each_layer_span_once(tiny_run):
    spans = tiny_run.spans
    by_id = {s.span_id: s for s in spans}
    bins = [s for s in spans if s.name == "exec.bin"]
    assert bins and len(bins) == len(tiny_run.ticks)
    assert all(b.args["jobs"] == 6 for b in bins)
    for name, parent in BIN_SPANS.items():
        got = [s for s in spans if s.name == name]
        assert len(got) == len(bins), name          # never one per job
        for s in got:
            assert by_id[s.parent_id].name == parent, name
            assert by_id[s.parent_id].t0 <= s.t0 <= s.t1 \
                <= by_id[s.parent_id].t1
    reads = [s for s in spans if s.name == "store.read_many"]
    assert [by_id[s.parent_id].name for s in reads] \
        == ["runtime.advance"] * len(bins)
    # every chunk of the feed lands in order: no read finds tail points
    # merged since the previous one
    assert sum(s.args["tail_points"] for s in reads) == 0


def test_span_readers_report_a_cpu_window(tiny_run):
    for name in ("persist_ms.score", "prepare_ms.score",
                 "device_wait_ms.score"):
        v = bench.load_reader(name)(tiny_run)
        assert v is not None and v > 0, name
    # an in-order feed merges no tail points, and the reader says so
    assert bench.load_reader("tail_sort_points.score")(tiny_run) == 0.0
    # no trace was recorded: the device reader has nothing to read
    assert bench.load_reader("idle_unattributed_ms.score")(tiny_run) is None


def test_an_out_of_order_append_shows_in_tail_sort_points():
    """A reading that lands behind its series' newest tail time is merged
    into the tail: the window's next read counts the points it moved."""
    from traffic import HOUR
    cell = bench.load_cell(ROOT, CELLS["lr"])
    cell["traffic"]["site"].update(n_prosumers=6, n_feeders=2)
    run = bench.Run(cell, 2 ** 31 + 17, require_chip=False)
    run.setup()
    store, ts_id = run.castor.store, run.site.ts_ids[-1]
    tail = store._data[ts_id]
    late = tail.tail_t[tail.tail_n - 1] - 1.5 * HOUR
    store.append(ts_id, [late], [1.0])
    assert store.tail_merges == 1
    run.window(0.2)
    got = bench.load_reader("tail_sort_points.score")(run)
    assert got == float(store.tail_sort_points) / len(run.ticks) > 0


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_spans_or_trace(name, tmp_path):
    run = SimpleNamespace(spans=[], ticks=[{"tick_s": 0.1}] * 3, trace=None,
                          cell={"name": "x", "bench": tmp_path / "bench"},
                          seed=1)
    read = bench.load_reader(name)
    assert read(run) is None
    run.trace = {"window_s": 1.0}          # traced, but no trace on disk
    assert read(run) is None


def test_innermost_span_wins():
    spans = [(0.0, 10.0, "bench.tick"), (1.0, 9.0, "castor.tick"),
             (2.0, 6.0, "exec.bin"), (3.0, 4.0, "store.write"),
             (7.0, 8.0, "journal.commit")]
    assert hs.innermost(spans) == [
        (0.0, 1.0, "bench.tick"), (1.0, 2.0, "castor.tick"),
        (2.0, 3.0, "exec.bin"), (3.0, 4.0, "store.write"),
        (4.0, 6.0, "exec.bin"), (6.0, 7.0, "castor.tick"),
        (7.0, 8.0, "journal.commit"), (8.0, 9.0, "castor.tick"),
        (9.0, 10.0, "bench.tick")]


def test_a_child_is_cut_at_its_parents_end():
    assert hs.innermost([(0.0, 2.0, "a.b"), (1.0, 3.0, "c.d")]) \
        == [(0.0, 1.0, "a.b"), (1.0, 2.0, "c.d")]


def test_attribute_splits_idle_time_by_innermost_span():
    spans = [(0.0, 10.0, "bench.tick"), (1.0, 9.0, "castor.tick"),
             (2.0, 6.0, "exec.bin"), (3.0, 4.0, "store.write"),
             (7.0, 8.0, "journal.commit"),
             (10.0, 12.0, "bench.ingest")]   # outside the ticks: not read
    devices = {"/device:TPU:0": [("fusion", 4.5, 5.5), ("while", 8.5, 11)]}
    got = hs.attribute(devices, spans)
    assert got["ticks"] == 1
    assert got["idle_s"] == pytest.approx(10.0 - 1.0 - 1.5)
    assert got["by_span"] == pytest.approx({
        "bench.tick": 1.0, "castor.tick": 2.0 + 0.5,
        "exec.bin": 1.0 + 0.5 + 0.5,
        "store.write": 1.0, "journal.commit": 1.0})
    # time under only castor.tick or bench.tick is nobody's layer
    assert got["unattributed_s"] == pytest.approx(3.5)


def test_attribute_needs_the_programs_spans():
    devices = {"/device:TPU:0": [("fusion", 1.0, 2.0)]}
    assert hs.attribute(devices, [(0.0, 3.0, "bench.tick")]) is None
    assert hs.attribute({}, [(0.0, 3.0, "bench.tick"),
                             (0.5, 2.5, "castor.tick")]) is None


def test_recorded_chip_trace_names_every_layer():
    """The new spans land in the host plane of a v5e trace, nested as in
    the span ring, and name nearly all of the device's idle time."""
    spans = hs.read_spans(RECORDED)
    names = [n for _, _, n in spans]
    assert names.count("bench.tick") == names.count("castor.tick") == 2
    for name in BIN_SPANS:
        assert names.count(name) == 2, name
    assert names.count("store.read_many") == 2

    def parent(child):
        a, b, _ = child
        return min((s for s in spans if s[0] <= a and b <= s[1]
                    and s is not child), key=lambda s: s[1] - s[0])[2]
    for s in spans:
        if s[2] in BIN_SPANS:
            assert parent(s) == BIN_SPANS[s[2]], s
    got = hs.read_trace(RECORDED)
    assert got["ticks"] == 2
    assert 0 < got["idle_s"]
    assert sum(got["by_span"].values()) == pytest.approx(got["idle_s"])
    assert got["unattributed_s"] <= 0.1 * got["idle_s"]
