"""The dense family's operation counts against the program's own
analytic arithmetic (``repro.roofline.analytic``) on both
configurations; ``flops.py``'s kernel counts and the peaks table."""

import pytest

import flops
import spec
from repro.models.config import ShapeSpec
from repro.roofline import analytic


@pytest.fixture(params=["stablelm-1.6b.chat", "granite-8b.code"])
def both(request):
    cell = spec.load_cell(request.param)
    return cell.k, cell.family.program_config(cell.config), cell.family


@pytest.mark.parametrize("ctx", [1, 384, 4096])
def test_decode_token_matches_analytic(both, ctx):
    k, arch, fam = both
    want = analytic.fwd_flops(arch, ShapeSpec("d", ctx, 1, "decode"))
    assert fam.decode_flops(k, ctx) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("n", [64, 1024, 4032])
def test_prefill_is_analytic_with_an_exact_triangle_and_one_logit_row(
        both, n):
    k, arch, fam = both
    # analytic counts every token's logits and each query at the mean
    # context n/2; the served prefill computes one row of logits and the
    # causal triangle holds n(n+1)/2 pairs, not n*n/2
    want = analytic.fwd_flops(arch, ShapeSpec("p", n, 1, "prefill"))
    want -= (n - 1) * 2 * k["d"] * k["vocab"]
    want += k["layers"] * 4 * k["heads"] * k["head_dim"] * n / 2
    assert fam.prefill_flops(k, n) == pytest.approx(want, rel=1e-12)


def test_dense_part_is_twice_the_layer_parameters(both):
    k, arch, fam = both
    emb = arch.vocab * arch.d_model * (1 if arch.tie_embeddings else 2)
    assert fam.dense_per_token(k) == 2 * (arch.param_count() - emb)


def test_flash_counts_the_causal_triangle():
    # 2 heads, 3 tokens, width 4: pairs (0,0) (1,0) (1,1) (2,0..2) = 6,
    # each a 4-wide dot for the score and 4 multiply-adds for the value
    assert flops.flash_flops(2, 3, 4) == 2 * 6 * (2 * 4 + 2 * 4)
    assert flops.flash_bytes(2, 3, 4, 2) == 4 * 2 * 3 * 4 * 2


def test_roofline_takes_the_larger_bound():
    peak = flops.load_peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert flops.roofline_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert flops.roofline_s(1.0, 819e9, peak) == pytest.approx(1.0)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.load_peaks("cpu")
