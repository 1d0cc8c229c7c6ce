"""The analytic LM roofline (repro.core.lmtime): its three terms, the HBM
fit and the software knobs that move them."""

from repro.configs.base import SHAPES, get_arch
from repro.core.lmtime import HW, MeshPlan, lm_roofline
from repro.models.model import active_params, count_params


def _cell(arch, shape):
    cfg = get_arch(arch)
    return cfg, SHAPES[shape], count_params(cfg), active_params(cfg)


def test_roofline_terms_positive_and_bounded():
    cfg, shape, n, na = _cell("llama3-8b", "train_4k")
    r = lm_roofline(cfg, shape, MeshPlan(1, 16, 16, 8, "full", False), n, na)
    assert r["compute_s"] > 0 and r["memory_s"] > 0 and r["collective_s"] > 0
    # compute term must be >= ideal 6ND/peak (recompute only adds)
    ideal = 6 * na * shape.tokens / (256 * HW["peak_flops_bf16"])
    assert r["compute_s"] >= ideal * 0.99


def test_fsdp_required_for_huge_models():
    """deepseek at TP-16 without FSDP cannot fit HBM; with FSDP it must."""
    cfg, shape, n, na = _cell("deepseek-v3-671b", "train_4k")
    no = lm_roofline(cfg, shape, MeshPlan(1, 16, 16, 32, "full", False), n, na)
    yes = lm_roofline(cfg, shape, MeshPlan(1, 16, 16, 32, "full", True), n, na)
    assert not no["fits"]
    assert yes["hbm_bytes"] < no["hbm_bytes"]


def test_compression_reduces_collective_term():
    cfg, shape, n, na = _cell("llama3-8b", "train_4k")
    plain = lm_roofline(cfg, shape, MeshPlan(2, 8, 16, 8, "full", False, False), n, na)
    comp = lm_roofline(cfg, shape, MeshPlan(2, 8, 16, 8, "full", False, True), n, na)
    assert comp["collective_s"] < plain["collective_s"]
