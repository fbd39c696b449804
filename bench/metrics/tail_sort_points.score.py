"""Store reads: tail points that out-of-order appends merged, per window
tick. A chunk that lands before its series' newest tail time is merged
into the sorted tail, moving the tail's points; the store counts them
(``tail_sort_points``) and each ``store.read_many`` span carries the
count gained since the previous one as ``tail_points``. In-order appends
and the reads themselves sort nothing, so a feed that lands in order
reads 0."""


def read(run):
    pts = [s.args["tail_points"] for s in run.spans
           if s.name == "store.read_many" and s.args
           and "tail_points" in s.args]
    if not pts or not run.ticks:
        return None
    return float(sum(pts)) / len(run.ticks)
