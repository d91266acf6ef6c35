"""Of the executables the start needed (``sonata_compile_total`` with
``phase="backend"`` as the window began), the share it loaded from the
persistent cache (``cache="hit"``) and did not compile: near 100 on a warm
start, near 0 on a cold one."""

from perfbench.harness import compiles


def read(run):
    before = run["metrics_before"]
    needed = compiles.total(before, compiles.COUNT, 'phase="backend"')
    if not needed:
        return None
    return 100.0 * compiles.total(before, compiles.COUNT, 'phase="backend"',
                                  'cache="hit"') / needed
