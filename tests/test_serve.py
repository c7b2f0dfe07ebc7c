"""Serving-loop tests: batched generation, greedy determinism,
deterministic compensated cross-device reduction."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke
from repro.train import ServeConfig, Server


def _prompt_batch(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                   jnp.int32)}
    if cfg.vision is not None:
        batch["vision_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.vision.n_patches, cfg.d_model)),
            jnp.float32)
    if cfg.encoder is not None:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.encoder.n_frames, cfg.d_model)),
            jnp.float32)
    return batch


def test_greedy_generation_is_deterministic():
    cfg = get_smoke("olmo-1b")
    server = Server(cfg, ServeConfig(temperature=0.0))
    batch = _prompt_batch(cfg)
    out1 = server.generate(batch, 6)
    out2 = server.generate(batch, 6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 6)
    assert int(jnp.max(out1)) < cfg.padded_vocab


def test_generation_differs_across_prompts():
    cfg = get_smoke("qwen2.5-3b")
    server = Server(cfg, ServeConfig(temperature=0.0))
    b1 = _prompt_batch(cfg, seed=1)
    b2 = _prompt_batch(cfg, seed=2)
    o1 = server.generate(b1, 5)
    o2 = server.generate(b2, 5)
    assert not np.array_equal(np.asarray(o1), np.asarray(o2))


def test_compensated_psum_scalar_single_device():
    from repro.core.kahan import compensated_psum_scalar

    mesh = jax.make_mesh((1,), ("data",))

    @jax.jit
    def run(s, c):
        return jax.shard_map(
            lambda a, b: compensated_psum_scalar(a[0], b[0], "data"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(  # fold result is replicated by construction
                s[None], c[None])

    s, c = run(jnp.float32(1e8), jnp.float32(1.0))
    assert float(s) + float(c) == 1e8 + 1.0
