"""Sharding rules (pure-function tests on AbstractMesh) + roofline parser +
cost-fit algebra (no compiles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.analysis.costfit import basis_row
from repro.analysis.roofline import collective_bytes
from repro.distributed.sharding import _with_fsdp, param_pspec

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestParamShardingRules:
    def test_ffn_tp(self):
        assert param_pspec("groups/0/stacked/b0/ffn/w1", (56, 6144, 16384),
                           MESH) == P(None, None, "model")
        assert param_pspec("groups/0/stacked/b0/ffn/w2", (56, 16384, 6144),
                           MESH) == P(None, "model", None)

    def test_moe_expert_dff_tp(self):
        # (R, E, d, f): shard f — works for 8 experts on a 16-way axis
        assert param_pspec("groups/0/stacked/b0/ffn/w1",
                           (56, 8, 6144, 16384), MESH) \
            == P(None, None, None, "model")
        assert param_pspec("groups/0/stacked/b0/ffn/w2",
                           (56, 8, 16384, 6144), MESH) \
            == P(None, None, "model", None)

    def test_attention_projections(self):
        assert param_pspec("groups/0/stacked/b0/mixer/wq/w", (56, 6144, 6144),
                           MESH) == P(None, None, "model")
        assert param_pspec("groups/0/stacked/b0/mixer/wo/w", (56, 6144, 6144),
                           MESH) == P(None, "model", None)

    def test_indivisible_replicates(self):
        # kv proj output 1024 = 8 heads x 128: divisible; 8 x 80 = 640 not
        assert param_pspec("g/mixer/wk/w", (24, 2560, 640), MESH) \
            == P(None, None, "model") if 640 % 16 == 0 else True
        assert param_pspec("g/mixer/wk/w", (24, 2560, 200), MESH) \
            == P(None, None, None)

    def test_norms_replicated(self):
        assert param_pspec("groups/0/stacked/b0/ln1", (56, 6144), MESH) \
            == P(None, None)

    def test_embed_vocab_sharded(self):
        assert param_pspec("embed", (32768, 6144), MESH) == P("model", None)
        assert param_pspec("unembed", (6144, 32768), MESH) \
            == P(None, "model")

    def test_fsdp_adds_data_axis(self):
        spec = param_pspec("groups/0/stacked/b0/ffn/w1", (56, 6144, 16384),
                           MESH, fsdp=True)
        assert "data" in spec and "model" in spec

    def test_fsdp_skips_small(self):
        spec = _with_fsdp(P(None), (8,), MESH)
        assert spec == P(None)


class TestRooflineParser:
    HLO = """
  %ag = bf16[2048,512]{1,0} all-gather(%p0), replica_groups={...}
  %ar = f32[1024]{0} all-reduce(%x), to_apply=%add
  %rs.1 = bf16[64,128]{1,0} reduce-scatter(%y), dimensions={0}
  %a2a = (f32[16,16]{1,0}, f32[16,16]{1,0}) all-to-all(%a, %b)
  %cp = u32[8]{0} collective-permute(%c), source_target_pairs={{0,1}}
  %fusion.1 = bf16[999,999]{1,0} fusion(%q), kind=kLoop
"""

    def test_collective_bytes(self):
        out = collective_bytes(self.HLO)
        assert out["all-gather"] == 2048 * 512 * 2
        assert out["all-reduce"] == 1024 * 4
        assert out["reduce-scatter"] == 64 * 128 * 2
        assert out["all-to-all"] == 2 * 16 * 16 * 4
        assert out["collective-permute"] == 8 * 4
        assert out["count"] == 5
        assert out["total"] == sum(out[k] for k in (
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute"))

    def test_non_collectives_ignored(self):
        assert collective_bytes("%f = bf16[4,4]{1,0} fusion(%x)")["total"] == 0


class TestCostFitAlgebra:
    def test_exact_recovery_of_planted_polynomial(self):
        """lstsq over the probe basis recovers a planted cost model exactly
        and extrapolates to full scale."""
        rng = np.random.default_rng(0)
        true = rng.uniform(1, 10, size=len(basis_row("train", 1, 1, (1,), 1)))

        def F(B, S, r, mb):
            return float(np.dot(true, basis_row("train", B, S, r, mb)))

        plan = [(16, s, (r,), m) for s in (2048, 4096, 8192)
                for r in (1, 2) for m in (1,)]
        plan += [(32, 2048, (1,), 1), (32, 2048, (2,), 1),
                 (32, 2048, (1,), 2), (32, 4096, (2,), 2)]
        A = np.stack([basis_row("train", *p) for p in plan])
        y = np.array([F(*p) for p in plan])
        scale = np.maximum(np.abs(A).max(0), 1e-12)
        c, *_ = np.linalg.lstsq(A / scale, y, rcond=None)
        c = c / scale
        # extrapolate far outside the probe grid
        got = float(np.dot(c, basis_row("train", 256, 32768, (56,), 16)))
        want = F(256, 32768, (56,), 16)
        assert abs(got / want - 1) < 1e-6

    def test_mesh_fn_no_device_state(self):
        """Importing mesh.py must not initialize jax devices (the dry-run
        sets XLA_FLAGS first)."""
        import importlib

        import repro.launch.mesh as m
        importlib.reload(m)
        assert callable(m.make_production_mesh)


class TestKVByteAccounting:
    def test_incremental_bytes(self):
        from repro.configs import get_config
        from repro.models.kvcache import kv_bytes, kv_bytes_incremental
        cfg = get_config("kimi-linear-1t")
        full = kv_bytes(cfg, 32768)
        inc = kv_bytes_incremental(cfg, 16384, 32768)
        assert inc < full
        # incremental transfer still resends the O(1) linear state
        state = sum(b.mixer.state_bytes() for *_, b in cfg.iter_blocks()
                    if not hasattr(b.mixer, "q_heads"))
        assert inc == pytest.approx(full - kv_bytes(cfg, 16384) + state)

    def test_paper_table5_calibration(self):
        """kimi-linear-1t proxy S_kv matches the paper's Table 5 within 2%."""
        from repro.configs import get_config
        cfg = get_config("kimi-linear-1t")
        paper = {1024: 190.8, 8192: 308.9, 32768: 701.3, 131072: 2316.3}
        for l, mib in paper.items():
            ours = cfg.kv_cache_bytes(l) / 2**20
            assert abs(ours / mib - 1) < 0.02, (l, ours, mib)
