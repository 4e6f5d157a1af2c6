"""Jaxpr matmul-FLOP counter (homebrewnlp_tpu/utils/flops.py) — feeds the
MFU numbers the program reports."""
import jax
import jax.numpy as jnp

from backend import make_params  # noqa: F401  (sets up the CPU mesh env)


def flops_counter_test():
    """The jaxpr matmul-FLOP counter handles dots, scans (x length), and
    batched dot_general."""
    from homebrewnlp_tpu.utils.flops import forward_flops

    a = jnp.zeros((8, 16))
    b = jnp.zeros((16, 4))
    assert forward_flops(lambda x, y: x @ y, a, b) == 2 * 8 * 16 * 4

    bm = jnp.zeros((3, 8, 16))
    wm = jnp.zeros((3, 16, 4))
    assert forward_flops(lambda x, y: jnp.einsum("bij,bjk->bik", x, y),
                         bm, wm) == 3 * 2 * 8 * 16 * 4

    def scanned(x, y):
        def body(c, _):
            return c @ y, None
        out, _ = jax.lax.scan(body, x, jnp.arange(5))
        return out
    sq = jnp.zeros((16, 16))
    assert forward_flops(scanned, sq, sq) == 5 * 2 * 16 ** 3


def flops_split_causal_flash_test():
    """count_matmul_flops_split: full keeps the stable full-square
    convention; executed subtracts the causally-dead pallas cells.  For a
    causal grid of n x n blocks, live pairs = n(n+1)/2, so executed/full of
    the kernel's own FLOPs is (n+1)/(2n)."""
    from homebrewnlp_tpu.parallel.flash_attention import flash_attention
    from homebrewnlp_tpu.utils.flops import forward_flops_split

    b, s, h, d, blk = 1, 64, 1, 16, 16  # 4 x 4 block grid
    q = jnp.zeros((b, s, h, d))

    def fwd(causal):
        return lambda x: flash_attention(x, x, x, 1.0, causal, blk, blk, True)

    full_c, exec_c = forward_flops_split(fwd(True), q)
    full_nc, exec_nc = forward_flops_split(fwd(False), q)
    # non-causal: nothing skipped
    assert full_nc == exec_nc
    # same full-square count either way (stable convention)
    assert full_c == full_nc
    # causal executed: 10 of 16 cells live -> kernel FLOPs scale by 10/16
    n = s // blk
    live_frac = (n + 1) / (2 * n)
    assert exec_c == int(full_c * live_frac)


def flops_split_map_mixer_batch_sweep_grid_test():
    """The map mixer's dbias kernel runs a grid (heads, s blocks, t blocks,
    batch); the counter takes the block pair from the two middle dimensions
    and every other dimension as a multiplier, so forward + backward at a
    4 x 4 causal grid of tiles count what the (batch·heads, a, b) grids
    counted: three contractions of 2·b·h·s²·f = 50,331,648 each in full,
    and 10 of 16 cells of each executed."""
    from homebrewnlp_tpu.parallel.map_mixer import map_mixer
    from homebrewnlp_tpu.utils.flops import forward_flops_split

    h, s, f, b, blk = 2, 512, 16, 3, 128
    bias = jnp.zeros((h, s, s))
    v = jnp.zeros((b * h, s, f))

    def fwd_bwd(causal):
        return jax.grad(lambda bias_, v_: jnp.sum(
            map_mixer(bias_, v_, causal, blk, blk, True) ** 2),
            argnums=(0, 1))

    assert forward_flops_split(fwd_bwd(True), bias, v) \
        == (150_994_944, 94_371_840)
    assert forward_flops_split(fwd_bwd(False), bias, v) \
        == (150_994_944, 150_994_944)
