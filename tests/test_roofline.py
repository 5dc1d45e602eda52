"""Roofline machinery: HLO collective parsing, terms, analytic-model
validation against XLA cost_analysis on unrolled configs."""
import jax
import jax.numpy as jnp
import pytest

from repro import roofline
from repro.configs import ARCHS, SHAPES
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.models.transformer import segments_for

FAKE_HLO = """
HloModule test

%body.1 (p: (f32[8,16])) -> (f32[8,16]) {
  %ar = f32[8,16]{1,0} all-reduce(%x), replica_groups={}
  ROOT %t = (f32[8,16]) tuple(%ar)
}

%cond.1 (p: (f32[8,16])) -> pred[] {
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[64,32]) -> f32[64,32] {
  %ag = f32[64,32]{1,0} all-gather(%a), dimensions={0}
  %w = (f32[8,16]) while(%init), condition=%cond.1, body=%body.1
  %cp = f32[4,4]{1,0} collective-permute(%b), source_target_pairs={{0,1}}
  ROOT %r = f32[64,32]{1,0} add(%ag, %ag)
}
"""


def test_parse_collectives_basic():
    st = roofline.parse_collectives(FAKE_HLO, loop_multiplier=1)
    assert st.bytes_by_kind["all-gather"] == 64 * 32 * 4
    assert st.bytes_by_kind["all-reduce"] == 8 * 16 * 4
    assert st.bytes_by_kind["collective-permute"] == 4 * 4 * 4


def test_parse_collectives_loop_scaling():
    st = roofline.parse_collectives(FAKE_HLO, loop_multiplier=10)
    # only the all-reduce lives in the while body
    assert st.bytes_by_kind["all-reduce"] == 10 * 8 * 16 * 4
    assert st.bytes_by_kind["all-gather"] == 64 * 32 * 4


def test_roofline_terms_dominance():
    t = roofline.roofline_terms(197e12, 100e9, 1e9)   # 1s compute
    assert t["dominant"] == "compute_s"
    t = roofline.roofline_terms(1e9, 819e9 * 2, 0)
    assert t["dominant"] == "memory_s"


def test_model_flops_conventions():
    shape_t = SHAPES["train_4k"]
    shape_d = SHAPES["decode_32k"]
    assert roofline.model_flops(None, shape_t, 10) == 6 * 10 * 256 * 4096
    assert roofline.model_flops(None, shape_d, 10) == 2 * 10 * 128


@pytest.mark.parametrize("name,kw", [
    ("granite-3-2b", dict(n_layers=2, d_model=512, n_heads=8, n_kv_heads=4,
                          head_dim=64, d_ff=1024, vocab=4096)),
    ("olmoe-1b-7b", dict(n_layers=2, d_model=512, n_heads=8, n_kv_heads=8,
                         head_dim=64, d_ff=256, vocab=4096)),
    ("xlstm-1.3b", dict(n_layers=4, d_model=512, n_heads=2, head_dim=256,
                        vocab=4096, slstm_every=2)),
])
def test_analytic_flops_vs_hlo(name, kw):
    """The analytic model (what the roofline uses) matches XLA's own count
    on fully-unrolled configs within 25% (HLO also counts transcendentals)."""
    cfg = ARCHS[name].replace(dtype="float32", unroll=True, remat="none",
                              attn_chunk=128, ssm_chunk=64, **kw)
    m = build_model(cfg)
    B, S = 2, 512
    params = jax.eval_shape(lambda: m.init(jax.random.key(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    compiled = jax.jit(m.loss).lower(params, batch).compile()
    hlo_flops = compiled.cost_analysis().get("flops", 0.0)
    shape = ShapeConfig("v", S, B, "train")
    ana = roofline.analytic_flops(cfg, shape, segments_for(cfg))
    ratio = ana["fwd_total"] / hlo_flops
    assert 0.75 <= ratio <= 1.25, ratio


def test_active_params_moe():
    arch = ARCHS["olmoe-1b-7b"]
    n = build_model(arch).param_count()
    na = roofline.active_params(arch, n)
    assert na < n
    # top-8 of 64 experts: expert block shrinks 8x
    assert na / n < 0.5
