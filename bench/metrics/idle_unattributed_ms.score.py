"""Device: idle time inside ``bench.tick`` in which the innermost program
span open on the host was ``castor.tick`` or ``bench.tick`` (host work no
layer's span claims), per traced tick, in ms. See ``host_spans``."""
import host_spans


def read(run):
    got = host_spans.for_run(run)
    if got is None or not got["ticks"]:
        return None
    return 1e3 * got["unattributed_s"] / got["ticks"]
