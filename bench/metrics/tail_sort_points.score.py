"""Store reads: points concatenated and sorted to rebuild store tails,
the ``tail_points`` of the window's ``store.read_many`` spans, per
window tick."""


def read(run):
    pts = [s.args["tail_points"] for s in run.spans
           if s.name == "store.read_many" and s.args
           and "tail_points" in s.args]
    if not pts or not run.ticks:
        return None
    return float(sum(pts)) / len(run.ticks)
