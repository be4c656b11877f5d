"""Share of the causal score entries that the selection keeps, as the
program counted when it built its select calls: ``dsa.entries.kept /
dsa.entries.causal`` (``ops/dsa_kernels._book``: from the length and
``topk`` alone, ties beyond ``topk`` not counted). The counters add up over
every build of the process; each build books the same counts, so the share
is the step's. Nothing to read in a program that does not count."""

from benchmark.lib.program import snapshot


def read(run):
    counters = snapshot()["counters"]
    kept = counters.get("dsa.entries.kept")
    causal = counters.get("dsa.entries.causal")
    if kept is None or not causal:
        return None
    return 100.0 * kept / causal
