"""Grid steps of the attention kernels for each tile they visit: the program's
counters ``attn.grid_steps`` over ``attn.tiles_visited``, written beside
``attn.tiles_total`` when the step is traced (the forward's and the backward's
grid of every layer, over all batch rows and query heads).  A kernel whose
grid walks a list of the visited tiles reads 1; one whose grid pads every
query tile's row of key tiles to the longest row's length reads the padded
steps on top: 144 / 80 = 1.8 under the block-diffusion mask over 16 x 16
tiles, 256 / 136 = 1.88 under the causal tables.  A step that visits no tile
computes nothing and still costs its turn in the grid.  Silent where the
program has no such counter."""


def read(run):
    try:
        from mxnet_tpu import profiler
        totals = profiler.totals()
        steps = totals["attn.grid_steps"]["count"]
        visited = totals["attn.tiles_visited"]["count"]
    except Exception:
        return None
    return steps / visited if visited else None
