"""Roofline methodology tests: the HLO collective parser and the analytic FLOP
formulas (validated against XLA cost analysis on scan-free configurations,
where every trip count is 1 and the two must agree)."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo import collective_stats, _shape_bytes
from repro.roofline.flops import lm_flops
from repro.roofline.report import roofline_terms


class TestHloParser:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[128,256]") == 128 * 256 * 4
        assert _shape_bytes("bf16[2,3,4]") == 48
        assert _shape_bytes("(f32[8], s32[4])") == 32 + 16
        assert _shape_bytes("pred[100]") == 100
        assert _shape_bytes("f32[]") == 4

    def test_parses_synthetic_hlo(self):
        txt = """
  %ar = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups=[8,8]<=[64]
  %ag = bf16[64,128]{1,0} all-gather(%y), replica_groups=[4,16]<=[64], dimensions={0}
  %aa = s32[256]{0} all-to-all(%z), replica_groups=[1,64]<=[64]
  %cp = f32[32]{0} collective-permute(%w), source_target_pairs={{0,1}}
"""
        st = collective_stats(txt)
        assert st["counts"] == {
            "all-reduce": 1, "all-gather": 1, "all-to-all": 1,
            "collective-permute": 1,
        }
        assert st["out_bytes"]["all-reduce"] == 4096
        g = 8
        assert abs(st["wire_bytes"]["all-reduce"] - 2 * 4096 * (g - 1) / g) < 1
        assert st["out_bytes"]["all-gather"] == 64 * 128 * 2

    def test_real_lowered_collectives(self):
        """An einsum contracting a sharded dim must produce an all-reduce whose
        parsed bytes match the result tensor."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh

        if jax.device_count() < 1:
            pytest.skip("no devices")
        mesh = make_test_mesh((1,), ("model",))
        x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        w = jax.ShapeDtypeStruct((16, 4), jnp.float32)
        jf = jax.jit(
            lambda a, b: a @ b,
            in_shardings=(
                NamedSharding(mesh, P(None, "model")),
                NamedSharding(mesh, P("model", None)),
            ),
        )
        txt = jf.lower(x, w).compile().as_text()
        st = collective_stats(txt)
        # single-device mesh -> partitioner may elide; just ensure no crash
        assert "wire_bytes_total" in st


class TestAnalyticFlops:
    def test_matches_hlo_on_scan_free_config(self):
        """With L=1 and S <= chunk (all trip counts 1), XLA's HLO flop count
        must agree with the analytic formula to ~15% (XLA adds elementwise)."""
        from repro.models.transformer import TransformerConfig, init_params, forward, logits_fn

        cfg = TransformerConfig(
            name="probe", n_layers=1, d_model=256, n_heads=4, n_kv_heads=4,
            d_ff=512, vocab=1024, chunk_q=64, chunk_k=64, dtype=jnp.float32,
        )
        B, S = 2, 64
        params = jax.eval_shape(
            lambda k: init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        toks = jax.ShapeDtypeStruct((B, S), jnp.int32)

        def fwd(p, t):
            h, _ = forward(p, cfg, t)
            return logits_fn(p, cfg, h)

        ca = jax.jit(fwd).lower(params, toks).compile().cost_analysis()
        hlo = float(ca["flops"])
        analytic = lm_flops(cfg, "prefill", B, S) + (
            2 * B * S * cfg.d_model * cfg.vocab - 2 * B * cfg.d_model * cfg.vocab
        )  # probe computes logits at ALL positions, formula only at last
        assert abs(hlo - analytic) / analytic < 0.15, (hlo, analytic)

    def test_train_multiplier(self):
        from repro.models.transformer import TransformerConfig

        cfg = TransformerConfig(
            name="m", n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
            d_ff=128, vocab=100, remat=False,
        )
        f_fwd = lm_flops(cfg, "prefill", 4, 32) + 2 * (4 * 32 - 4) * 64 * 100
        f_train = lm_flops(cfg, "train", 4, 32)
        assert abs(f_train - 3 * f_fwd) / f_train < 0.01

    def test_moe_scales_with_capacity(self):
        from repro.models.transformer import MoESettings, TransformerConfig

        base = {"name": "m", "n_layers": 2, "d_model": 64, "n_heads": 2,
                "n_kv_heads": 2, "d_ff": 128, "vocab": 100}
        c1 = TransformerConfig(**base, moe=MoESettings(8, 2, 64, 0, 1.0))
        c2 = TransformerConfig(**base, moe=MoESettings(8, 2, 64, 0, 2.0))
        assert lm_flops(c2, "prefill", 4, 128) > lm_flops(c1, "prefill", 4, 128)


class TestRooflineTerms:
    def test_bound_detection(self):
        rec = {
            "cost": {"flops": 1e12, "bytes_accessed": 1e9},
            "collectives": {"wire_bytes_total": 1e6},
            "chips": 256,
            "model_flops": 0.5e12 * 256,
        }
        t = roofline_terms(rec)
        assert t["bound"] == "compute"
        assert t["compute_s"] == pytest.approx(1e12 / 197e12)
        assert 0 < t["roofline_fraction"] <= 1.0
