"""The share of the attention kernels' tiles that hold a visible pair and are
visited, of all tiles of the square: the program's counters
``attn.tiles_visited`` over ``attn.tiles_total``, written when the step is
traced (forward and both backward grids, every layer).  About a quarter of the
square is visible under the block-diffusion mask; the share says how close the
tiling comes to that.  Silent where the program has no such counters."""


def read(run):
    try:
        from mxnet_tpu import profiler
        totals = profiler.totals()
        visited = totals["attn.tiles_visited"]["count"]
        total = totals["attn.tiles_total"]["count"]
    except Exception:
        return None
    return 100.0 * visited / total if total else None
