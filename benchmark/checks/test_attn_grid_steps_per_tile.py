"""``attn_grid_steps_per_tile.train`` over the program's recorder: a grid that
takes a step for each visited tile, one that pads each row of tiles to the
longest, a program that counts no grid steps (the reader's parent), and one
with no attention kernel at all.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402

# 32 heads, forward and backward, six layers
CALLS = 32 * 2 * 6


@pytest.mark.parametrize("counts,reads", [
    ({"attn.tiles_total": 256, "attn.tiles_visited": 80,
      "attn.grid_steps": 80}, 1.0),
    ({"attn.tiles_total": 256, "attn.tiles_visited": 80,
      "attn.grid_steps": 144}, 1.8),
    ({"attn.tiles_total": 256, "attn.tiles_visited": 136,
      "attn.grid_steps": 256}, 256 / 136),
    ({"attn.tiles_total": 256, "attn.tiles_visited": 80}, None),
    ({"attn.tiles_total": 256, "attn.tiles_visited": 0,
      "attn.grid_steps": 16}, None),
    ({}, None)])
def test_grid_steps_for_each_visited_tile(counts, reads):
    profiler.reset_spans()
    for name, n in counts.items():
        profiler.count(name, CALLS * n)
    read = harness.load_reader("attn_grid_steps_per_tile.train")
    assert read({"trace": None}) == reads
    profiler.reset_spans()


def test_the_kernels_count_a_step_for_each_entry_of_their_list():
    """The program's side: one call's plan under the block-diffusion mask."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_ops
    profiler.reset_spans()
    plan = pallas_ops._Plan(
        (1, 8, 256, 16), (1, 1, 256, 16),
        pallas_ops.block_diffusion_mask(128, 4), 0.25, 32, 32, jnp.float32,
        True)
    plan.count_tiles()
    read = harness.load_reader("attn_grid_steps_per_tile.train")
    assert read({"trace": None}) == 1.0
    profiler.reset_spans()
