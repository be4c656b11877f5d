"""Share of the causal flash kernels' compute tiles that the tile geometry
skips (beyond the diagonal, or left of a window's band), as the program
counted when it built its calls: ``flash.tiles.skipped / (flash.tiles.
visited + flash.tiles.skipped)`` (``ops/pallas_kernels._book_tiles``, one
count a kernel and head-independent). The counters add up over every build
of the process; each build books the same counts, so the share is the
step's. Nothing to read in a program that does not count or built no
causal call."""

from benchmark.lib.program import snapshot


def read(run):
    counters = snapshot()["counters"]
    visited = counters.get("flash.tiles.visited")
    skipped = counters.get("flash.tiles.skipped")
    if visited is None or skipped is None or visited + skipped <= 0:
        return None
    return 100.0 * skipped / (visited + skipped)
