"""Flash attention + ring attention correctness vs the reference impl."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchft_tpu.models.transformer import plain_attention
from torchft_tpu.ops import flash_attention
from torchft_tpu.parallel import make_mesh
from torchft_tpu.parallel.ring_attention import make_ring_attention

# Compile-heavy tier: pallas interpret mode + sharded jit dominate suite
# wall-clock; scripts/test.sh runs these after the fast unit tier.
pytestmark = pytest.mark.heavy


def qkv(b=2, s=32, h=4, d=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = qkv()
        ref = plain_attention(q, k, v, causal)
        out = flash_attention(q, k, v, causal, 8, 8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_single_block(self):
        q, k, v = qkv(s=16)
        ref = plain_attention(q, k, v, True)
        out = flash_attention(q, k, v, True, 16, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_causal_cross_shape_end_aligned(self):
        # s_q != s_k (decode-style): queries are the LAST s_q positions.
        # Forward and backward must use the same end-aligned mask
        # (round-1 ADVICE: the kernel was start-aligned, the vjp end-aligned).
        b, h, d = 2, 4, 16
        ks = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(ks[0], (b, 8, h, d))
        k = jax.random.normal(ks[1], (b, 32, h, d))
        v = jax.random.normal(ks[2], (b, 32, h, d))
        ref = plain_attention(q, k, v, True)
        out = flash_attention(q, k, v, True, 8, 8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        gf = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, 8, 8) ** 2), (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            plain_attention(q, k, v, True) ** 2), (0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("s", [9, 100, 999])
    def test_odd_seq_lens_pad_exactly(self, s):
        """ADVICE r2: lengths with no sublane-aligned dividing tile are
        end-padded (q and k equally) instead of leaning on Mosaic's
        implicit padding; forward AND grads must match the reference
        bitwise-closely."""
        q, k, v = qkv(s=s)
        ref = plain_attention(q, k, v, True)
        out = flash_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        gf = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True) ** 2), (0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            plain_attention(q, k, v, True) ** 2), (0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4, rtol=2e-4)

    def test_odd_seq_non_causal_raises(self):
        # ValueError (not assert): must survive `python -O`.
        q, k, v = qkv(s=999)
        with pytest.raises(ValueError, match="aligned"):
            flash_attention(q, k, v, False)

    def test_grads_match_reference(self):
        q, k, v = qkv(s=16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 8, 8) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(plain_attention(q, k, v, True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)


class TestHeadOf64:
    """LFM2-8B-A1B's attention layer: a head of 64 (2048 / 32), 32 query
    heads on 8 key/value heads: every other configuration runs heads of 128
    to 256. The interpreted kernels (forward, and the split backward's two:
    interpreted, ``_flash_bwd`` always takes them) against the module's own
    ``_reference`` at a q grid four blocks deep; the fused backward at
    this head size is interpreted in ``tests/test_flash_band.py`` and run
    on the chip by ``scripts/flash_head64_check.py``."""

    B, S, H, HKV, D, BLOCK = 1, 256, 32, 8, 64, 64

    def _inputs(self):
        ks = jax.random.split(jax.random.key(7), 4)
        return (jax.random.normal(ks[0], (self.B, self.S, self.H, self.D)),
                jax.random.normal(ks[1], (self.B, self.S, self.HKV, self.D)),
                jax.random.normal(ks[2], (self.B, self.S, self.HKV, self.D)),
                jax.random.normal(ks[3], (self.B, self.S, self.H, self.D)))

    def _both(self):
        from torchft_tpu.ops.flash_attention import _reference

        q, k, v, g = self._inputs()
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, True, self.BLOCK, self.BLOCK, interpret=True), q, k, v)
        rep = self.H // self.HKV     # _reference takes equal head counts
        want, vjp_ref = jax.vjp(
            lambda q, k, v: _reference(q, jnp.repeat(k, rep, axis=2),
                                       jnp.repeat(v, rep, axis=2), True),
            q, k, v)
        return (out, *vjp(g)), (want, *vjp_ref(g))

    @pytest.mark.parametrize("which", ["forward", "dq", "dk", "dv"])
    def test_against_the_reference(self, which):
        got, want = self._both()
        i = ["forward", "dq", "dk", "dv"].index(which)
        assert got[i].shape == want[i].shape
        assert got[i].shape[2] == (self.H if i < 2 else self.HKV)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[i]),
                                   atol=2e-5 if i == 0 else 1e-4)

    def test_the_tiles_of_a_head_of_64_are_a_head_of_128s(self):
        """No tile rule of its own: at 8,192 tokens a head of 64 takes the
        1,024-token tiles a head of 128 takes (a head over 128 takes 512),
        so the q grid is eight deep and the backward is the fused one."""
        from torchft_tpu.ops.flash_attention import _auto_block

        assert _auto_block(8192, cap=1024) == 1024
        assert 8192 // _auto_block(8192, cap=1024) >= 4


class TestSixteenHeadsOf128:
    """Ouro-2.6B's attention (PR 56): plain multi-head attention, 16 query
    heads on 16 key/value heads of 128: a group of ONE at a head of 128,
    which no other cell's plain kernels run (``(bh % h) // rep`` with
    ``rep`` 1, no dk/dv sum over a group). Forward, the split backward
    (interpreted, ``_flash_bwd`` takes it) and the fused backward (told it
    compiles, every ``pallas_call`` interpreted, as
    ``tests/test_flash_band.py`` does) against ``plain_attention`` at a q
    grid four blocks deep."""

    B, S, H, D, BLOCK = 1, 256, 16, 128, 64

    @pytest.mark.parametrize("which", ["forward", "split", "fused"])
    def test_against_plain_attention(self, which, monkeypatch):
        import importlib

        fa = importlib.import_module("torchft_tpu.ops.flash_attention")
        q, k, v = qkv(b=self.B, s=self.S, h=self.H, d=self.D, seed=3)
        g = jax.random.normal(jax.random.key(4), q.shape)
        want, vjp = jax.vjp(lambda *a: plain_attention(*a, True), q, k, v)
        if which == "forward":
            got = flash_attention(q, k, v, True, self.BLOCK, self.BLOCK,
                                  interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-5)
            return
        real = fa.pl.pallas_call
        calls = []

        def call(*a, **kw):
            calls.append(kw.get("name"))
            return real(*a, **{**kw, "interpret": True})

        monkeypatch.setattr(fa.pl, "pallas_call", call)
        monkeypatch.delenv("TORCHFT_FLASH_FUSED_BWD", raising=False)
        out, lse = fa._flash_fwd(q, k, v, True, self.BLOCK, self.BLOCK, True)
        del calls[:]
        got = fa._flash_bwd(q, k, v, out, lse, g, True, self.BLOCK,
                            self.BLOCK, interpret=which == "split")
        assert len(calls) == (2 if which == "split" else 1)
        for a, b in zip(got, vjp(g)):
            assert a.shape == b.shape == q.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)


class TestFlashAttentionGQA:
    """GQA/MQA kv heads are shared via kernel index maps — values and
    gradients must match the materialized-repeat path exactly."""

    def test_ring_attention_gqa(self):
        """Ring attention with GQA kv heads matches plain attention; the
        ring rotates the SMALL kv tensors."""
        from torchft_tpu.models.transformer import plain_attention
        from torchft_tpu.parallel import make_ring_attention
        from torchft_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
        ring = make_ring_attention(mesh, axis="sp", batch_axes=())
        assert ring.supports_gqa
        q, _, _ = qkv(s=32, h=8)
        _, k, v = qkv(s=32, h=2, seed=5)
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P(None, "sp", None, None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        out = ring(qs, ks, vs, True)
        ref = plain_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("h_kv", [1, 2])
    def test_matches_repeat_path(self, h_kv):
        q, _, _ = qkv(s=32, h=8)
        _, k, v = qkv(s=32, h=h_kv, seed=3)
        rep = 8 // h_kv

        def loss_gqa(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 8, 8) ** 2)

        def loss_rep(q, k, v):
            return jnp.sum(flash_attention(
                q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                True, 8, 8) ** 2)

        np.testing.assert_allclose(float(loss_gqa(q, k, v)),
                                   float(loss_rep(q, k, v)), rtol=1e-5)
        g1 = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_rep, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


    @pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
    @pytest.mark.parametrize("h,h_kv", [(7, 1), (14, 2)])
    def test_seven_query_heads_a_key_value_head(self, h, h_kv, window):
        """A group of 7 (no power of two, as every cell's before PR 51):
        the kernels share a key/value head by ``(bh % h) // rep`` and sum
        the group's dk/dv, with and without a window shorter than the
        sequence, in blocks the window does not end on: forward and all
        three gradients against plain attention."""
        q, _, _ = qkv(s=64, h=h, seed=1)
        _, k, v = qkv(s=64, h=h_kv, seed=2)

        def flash(q, k, v):
            return flash_attention(q, k, v, True, 16, 16, window=window)

        def plain(q, k, v):
            return plain_attention(q, k, v, True, window=window)

        np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                   np.asarray(plain(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            assert float(jnp.max(jnp.abs(b))) > 1e-3
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestFlashAttentionBlock:
    """The ring-attention building block: one flash pass against a K/V
    block with a TRACED mask shift, returning (out, lse) for
    online-softmax merging — differentiable through both outputs."""

    def test_shift_modes_match_reference(self):
        from torchft_tpu.ops.flash_attention import (_reference,
                                                     flash_attention_block)

        q, k, v = qkv(s=32)
        s = q.shape[1]
        out_f, _ = flash_attention_block(q, k, v, jnp.int32(s), 8, 8)
        np.testing.assert_allclose(
            np.asarray(out_f), np.asarray(_reference(q, k, v, False)),
            rtol=2e-5, atol=2e-5)
        out_c, _ = flash_attention_block(q, k, v, jnp.int32(0), 8, 8)
        np.testing.assert_allclose(
            np.asarray(out_c), np.asarray(_reference(q, k, v, True)),
            rtol=2e-5, atol=2e-5)
        # fully blocked: lse ~ -inf → zero weight when merged
        _, lse_b = flash_attention_block(q, k, v, jnp.int32(-s), 8, 8)
        assert float(jnp.max(lse_b)) < -1e29

    def test_merge_value_and_grads_match_dense(self):
        """Two blocks (one full, one diagonal-causal) merged via lse must
        equal dense attention over the concatenated keys — including
        gradients, which flow through the lse cotangent."""
        from torchft_tpu.ops.flash_attention import flash_attention_block

        q, k1, v1 = qkv(s=16)
        _, k2, v2 = qkv(s=16, seed=9)
        s = q.shape[1]
        b, _, h, _ = q.shape

        def per(w):
            return w.reshape(b, h, s).transpose(0, 2, 1)[..., None]

        def loss_merged(q, k1, v1, k2, v2):
            o1, l1 = flash_attention_block(q, k1, v1, jnp.int32(s), 8, 8)
            o2, l2 = flash_attention_block(q, k2, v2, jnp.int32(0), 8, 8)
            m = jnp.maximum(l1, l2)
            w1, w2 = jnp.exp(l1 - m), jnp.exp(l2 - m)
            out = (per(w1) * o1 + per(w2) * o2) / (per(w1) + per(w2))
            return jnp.sum(out ** 2)

        def loss_dense(q, k1, v1, k2, v2):
            kk = jnp.concatenate([k1, k2], axis=1)
            vv = jnp.concatenate([v1, v2], axis=1)
            scale = q.shape[-1] ** -0.5
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * scale
            qp = jnp.arange(s)[:, None]
            kp = jnp.arange(s)[None, :]
            mask = jnp.concatenate(
                [jnp.ones((s, s), bool), qp >= kp], axis=1)
            logits = jnp.where(mask[None, None], logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, vv) ** 2)

        np.testing.assert_allclose(
            float(loss_merged(q, k1, v1, k2, v2)),
            float(loss_dense(q, k1, v1, k2, v2)), rtol=1e-4)
        gm = jax.grad(loss_merged, argnums=(0, 1, 2, 3, 4))(
            q, k1, v1, k2, v2)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3, 4))(
            q, k1, v1, k2, v2)
        for a, b_ in zip(gm, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference_sp8(self, causal):
        mesh = make_mesh({"sp": 8})
        q, k, v = qkv(s=64)
        spec = NamedSharding(mesh, P(None, "sp", None, None))
        qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
        ring = make_ring_attention(mesh)
        out = jax.jit(lambda a, b, c: ring(a, b, c, causal))(qs, ks, vs)
        ref = plain_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_mixed_dp_sp(self):
        mesh = make_mesh({"dp": 2, "sp": 4})
        q, k, v = qkv(b=4, s=32)
        spec = NamedSharding(mesh, P("dp", "sp", None, None))
        qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
        ring = make_ring_attention(mesh, batch_axes=("dp",))
        out = jax.jit(lambda a, b, c: ring(a, b, c, True))(qs, ks, vs)
        ref = plain_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_differentiable(self):
        mesh = make_mesh({"sp": 8})
        q, k, v = qkv(s=32)
        ring = make_ring_attention(mesh)

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v, True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(plain_attention(q, k, v, True) ** 2)

        with mesh:
            gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        ge = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, ge):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_sp1_falls_back(self):
        mesh = make_mesh({"dp": 8, "sp": 1})
        q, k, v = qkv()
        ring = make_ring_attention(mesh)
        out = ring(q, k, v, True)
        ref = plain_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)


class TestTransformerWithRing:
    def test_transformer_sp_forward_and_grad(self):
        from torchft_tpu.models import (
            Transformer, TransformerConfig, causal_lm_loss)

        mesh = make_mesh({"dp": 2, "sp": 4})
        ring = make_ring_attention(mesh, batch_axes=("dp",))
        kw = dict(vocab_size=128, num_layers=2, embed_dim=64, num_heads=4,
                  dtype=jnp.float32)
        cfg_ring = TransformerConfig(attention_fn=ring, **kw)
        cfg_ref = TransformerConfig(**kw)
        tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, 128)
        params = Transformer(cfg_ref).init(jax.random.key(0), tokens)

        tok_sharded = jax.device_put(
            tokens, NamedSharding(mesh, P("dp", "sp")))
        with mesh:
            out_ring = jax.jit(
                lambda p, t: Transformer(cfg_ring).apply(p, t)
            )(params, tok_sharded)
        out_ref = Transformer(cfg_ref).apply(params, tokens)
        np.testing.assert_allclose(np.asarray(out_ring),
                                   np.asarray(out_ref),
                                   atol=2e-4, rtol=2e-4)

        with mesh:
            g_ring = jax.jit(jax.grad(
                lambda p, t: causal_lm_loss(
                    Transformer(cfg_ring).apply(p, t), t)
            ))(params, tok_sharded)
        g_ref = jax.grad(
            lambda p, t: causal_lm_loss(Transformer(cfg_ref).apply(p, t), t)
        )(params, tokens)
        flat_r = jax.tree_util.tree_leaves(g_ring)
        flat_e = jax.tree_util.tree_leaves(g_ref)
        for a, b in zip(flat_r, flat_e):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-4, rtol=3e-3)


@pytest.mark.nightly
@pytest.mark.slow
class TestFusedBwdHardware:
    """Recurring real-device check of the fused backward against the
    split one (dq held in VMEM across a grid row's sweep: what Mosaic
    makes of it only a chip shows; ``ops/fused_bwd_check.py``).

    Nightly, and slow as well so that it never sits in the per-commit
    tier-1 budget: the child process runs with JAX_PLATFORMS unset and
    initialises whatever accelerator the machine has, and decides by
    exit code (75 = no TPU, which is a skip HERE; ``chip_smoke.py`` runs
    the same comparison in-process, where no TPU is a failure). A chip
    belongs to one process, so the child is started only from a parent
    that holds no TPU backend — the suite's parent is pinned to the CPU
    by conftest.py."""

    def test_fused_matches_split_on_hardware(self):
        import subprocess
        import sys as _sys

        assert jax.default_backend() == "cpu", (
            "this process holds an accelerator; the child could not get "
            "the chip — run python -m torchft_tpu.ops.fused_bwd_check "
            "directly instead")
        env = dict(os.environ)
        # Undo the suite's forced-CPU config so the subprocess can see a
        # real TPU if one is attached.
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [_sys.executable, "-m", "torchft_tpu.ops.fused_bwd_check"],
            env=env, capture_output=True, text=True, timeout=600)
        if r.returncode == 75:
            pytest.skip("no TPU attached: " + r.stderr.strip())
        assert r.returncode == 0, (
            f"fused-vs-split hardware mismatch:\n{r.stdout}\n{r.stderr}")
