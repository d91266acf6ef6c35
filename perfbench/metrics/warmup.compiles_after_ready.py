"""Executables compiled or loaded after the server was ready and warm:
``sonata_compile_total`` with ``phase="backend"``, after less before.  That
is the window *and* the replay and drain behind it (``/metrics`` is read
again only after the replayed requests), on every thread, every program an
eager operation's small one included, whatever the compile took: what the
cache directory's file count cannot see."""

from perfbench.harness import compiles


def read(run):
    after = compiles.total(run["metrics_after"], compiles.COUNT,
                           'phase="backend"')
    if after is None:
        return None
    return after - (compiles.total(run["metrics_before"], compiles.COUNT,
                                   'phase="backend"') or 0.0)
