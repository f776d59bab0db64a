"""Diffusion family tests — clip/unet/vae (the last reference injection
families, module_inject/containers/{clip,unet,vae}.py) + spatial ops."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.diffusion import (
    AutoencoderVAE,
    CLIPConfig,
    CLIPTextEncoder,
    UNet2DCondition,
    UNetConfig,
    VAEConfig,
    diffusion_sharding_rules,
    timestep_embedding,
)
from deepspeed_tpu.ops.spatial import (
    nhwc_bias_add,
    nhwc_bias_add_add,
    nhwc_bias_add_bias_add,
)


def test_spatial_ops_match_manual():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 4, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((2, 4, 4, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(8), jnp.float32)
    b2 = jnp.asarray(rng.standard_normal(8), jnp.float32)
    np.testing.assert_allclose(nhwc_bias_add(x, b), x + b[None, None, None])
    np.testing.assert_allclose(nhwc_bias_add_add(x, b, y),
                               x + b[None, None, None] + y)
    np.testing.assert_allclose(
        nhwc_bias_add_bias_add(x, b, y, b2),
        x + b[None, None, None] + y + b2[None, None, None], atol=1e-6)


def test_timestep_embedding_properties():
    emb = timestep_embedding(jnp.asarray([0, 10, 500]), 64)
    assert emb.shape == (3, 64)
    # t=0 embeds to cos=1, sin=0 halves
    np.testing.assert_allclose(emb[0, :32], np.ones(32), atol=1e-6)
    np.testing.assert_allclose(emb[0, 32:], np.zeros(32), atol=1e-6)
    assert not np.allclose(emb[1], emb[2])


@pytest.fixture
def clip_cfg():
    return CLIPConfig(vocab_size=64, max_positions=16, width=32, layers=2,
                      heads=2)


def test_clip_text_encoder(clip_cfg):
    model = CLIPTextEncoder(clip_cfg)
    ids = np.arange(8, dtype=np.int32)[None].repeat(2, 0) % 64
    params = model.init(jax.random.PRNGKey(0), ids)
    out = jax.jit(lambda p, i: model.apply(p, i))(params, ids)
    assert out.shape == (2, 8, 32)
    assert np.isfinite(np.asarray(out)).all()


def test_unet_denoise_step(clip_cfg):
    ucfg = UNetConfig(in_channels=4, out_channels=4, block_channels=(16, 32),
                      attention_heads=2, cross_attention_dim=32,
                      norm_groups=4)
    unet = UNet2DCondition(ucfg)
    latents = jnp.asarray(np.random.default_rng(0)
                          .standard_normal((2, 8, 8, 4)), jnp.float32)
    t = jnp.asarray([1, 500])
    context = jnp.asarray(np.random.default_rng(1)
                          .standard_normal((2, 8, 32)), jnp.float32)
    params = jax.jit(unet.init)(jax.random.PRNGKey(0), latents, t, context)
    denoise = jax.jit(lambda p, l, tt, c: unet.apply(p, l, tt, c))
    out = denoise(params, latents, t, context)
    assert out.shape == latents.shape
    assert np.isfinite(np.asarray(out)).all()
    # conditioning matters: different context -> different noise prediction
    out2 = denoise(params, latents, t, context + 1.0)
    assert not np.allclose(np.asarray(out), np.asarray(out2))


def test_vae_roundtrip_shapes():
    vae = AutoencoderVAE(VAEConfig(base_channels=16, norm_groups=4))
    images = jnp.asarray(np.random.default_rng(0)
                         .standard_normal((2, 16, 16, 3)), jnp.float32)
    params = vae.init(jax.random.PRNGKey(0), images)
    recon, mean, logvar = vae.apply(params, images)
    assert recon.shape == images.shape
    assert mean.shape == (2, 4, 4, 4)  # 4x spatial reduction, 4 latents
    # encode/decode entry points (the DSVAE surface): encode gives the RAW
    # distribution; scaling applies to the sampled latent before decode
    m, lv = vae.apply(params, images, method=AutoencoderVAE.encode)
    img = vae.apply(params, m * vae.cfg.scaling_factor,
                    method=AutoencoderVAE.decode)
    assert img.shape == images.shape
    np.testing.assert_allclose(np.asarray(img), np.asarray(recon), atol=1e-5)


def test_diffusion_sharding_rules_match_params(clip_cfg):
    import re

    model = CLIPTextEncoder(clip_cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    rules = diffusion_sharding_rules()
    hits = set()
    for kp, _ in jax.tree_util.tree_leaves_with_path(params):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        for pat, _spec in rules:
            if re.search(pat, path):
                hits.add(pat)
    # qkv + fc1 col-parallel and out_proj + fc2 row-parallel all match
    assert len(hits) == len(rules), (hits, rules)


def test_latent_denoise_pipeline_compiles(clip_cfg):
    """CLIP conditioning -> UNet denoise -> VAE decode, one jit program
    (the CUDA-graph analog for the stable-diffusion serving path)."""
    ucfg = UNetConfig(block_channels=(16,), attention_heads=2,
                      cross_attention_dim=32, norm_groups=4)
    clip = CLIPTextEncoder(clip_cfg)
    unet = UNet2DCondition(ucfg)
    vae = AutoencoderVAE(VAEConfig(base_channels=16, norm_groups=4))

    ids = np.arange(8, dtype=np.int32)[None] % 64
    latents = jnp.asarray(np.random.default_rng(0)
                          .standard_normal((1, 4, 4, 4)), jnp.float32)
    p_clip = clip.init(jax.random.PRNGKey(0), ids)
    p_unet = unet.init(jax.random.PRNGKey(1), latents,
                       jnp.asarray([1]), jnp.zeros((1, 8, 32)))
    p_vae = vae.init(jax.random.PRNGKey(2),
                     jnp.zeros((1, 16, 16, 3)))

    @jax.jit
    def denoise_step(latents, ids):
        context = clip.apply(p_clip, ids)
        noise = unet.apply(p_unet, latents, jnp.asarray([10]), context)
        latents = latents - 0.1 * noise
        return vae.apply(p_vae, latents, method=AutoencoderVAE.decode)

    img = denoise_step(latents, ids)
    assert img.shape == (1, 16, 16, 3)
    assert np.isfinite(np.asarray(img)).all()
