"""Jaxpr matmul-FLOP counter (homebrewnlp_tpu/utils/flops.py) — feeds the
MFU numbers the program reports."""
import jax
import jax.numpy as jnp
import pytest

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


def _pallas_eqns(jaxpr, found=None):
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_eqns(sub, found)
    return found


@pytest.mark.parametrize("kernel,form", [
    ("flash_fwd_causal", "split"),
    ("flash_bwd_fused_causal", "dkv_resident"),
    ("flash_bwd_fused_causal", "dq_resident"),
    ("flash_bwd_dq_causal", "split"),
    ("flash_bwd_dkv_causal", "split")])
@pytest.mark.parametrize("s", [4096, 8192, 16384])
def flash_executed_flops_follow_the_scored_pairs_test(s, kernel, form,
                                                      monkeypatch):
    """PR 55: a cell the diagonal crosses scores its live part only, and the
    counter follows the kernels' own geometry.  At ``attention``'s tiles,
    with ``n = s / 1024``: the forward (1,024 x 2,048; the cell that starts
    where its k tile starts scores the first 1,024 keys) executes ``(n + 1)
    / 2n`` of the full square — 10 / 36 / 136 tiles of 1,024 x 1,024 at
    4,096 / 8,192 / 16,384, where its whole cells were 12 / 40 / 144 — and
    each backward kernel (1,024 x 1,024; a diagonal cell is three of its
    four quadrants) ``(2n + 1) / 4n``: 9 / 34 / 132 tiles for 10 / 36 / 136
    cells — the one pass the same five matmuls a pair whichever side it
    holds resident.  ``full`` stays the full-square convention."""
    from homebrewnlp_tpu.parallel import flash_attention as fa
    from homebrewnlp_tpu.utils.flops import (_StrippedJaxpr,
                                             count_matmul_flops_split)
    d, n = 128, s // 1024
    fused = form != "split"
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    x = jax.ShapeDtypeStruct((1, s, 1, d), jnp.bfloat16)
    blk, fwd_q, fwd_k, band = fa.call_tiles(s, d, None, 2)
    assert (blk, fwd_q, fwd_k, band) == (1024, 1024, 2048, False)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, 1.0, True, fwd_q, fwd_k, False,
                                  blk, blk).astype(jnp.float32).sum()

    eqns = _pallas_eqns(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x).jaxpr)
    assert sorted(eqns) == (["flash_bwd_fused_causal", "flash_fwd_causal"]
                            if fused else
                            ["flash_bwd_dkv_causal", "flash_bwd_dq_causal",
                             "flash_fwd_causal"])
    full, executed = count_matmul_flops_split(_StrippedJaxpr([eqns[kernel]]))
    dots = {"flash_fwd_causal": 2, "flash_bwd_fused_causal": 5,
            "flash_bwd_dq_causal": 3, "flash_bwd_dkv_causal": 4}[kernel]
    assert full == dots * 2 * s * s * d
    tile = dots * 2 * 1024 * 1024 * d
    if kernel == "flash_fwd_causal":
        assert executed == n * (n + 1) // 2 * tile
        assert fa.scored_pairs(s, fwd_q, fwd_k, carried=True) \
            == {4096: 10, 8192: 36, 16384: 136}[s] * 1024 * 1024
    else:
        assert executed * 4 == n * (2 * n + 1) * tile
        assert fa.scored_pairs(s, blk, blk) \
            == {4096: 9, 8192: 34, 16384: 132}[s] * 1024 * 1024
    # scored over live, as the gauge reads it: 1.25 / 1.125 at 4,096
    assert fa.scored_over_live(s, d, None, 2) == {
        "fwd": (n + 1) / n, "bwd": (2 * n + 1) / (2 * n)}
