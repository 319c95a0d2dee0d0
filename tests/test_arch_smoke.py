"""Per-architecture smoke tests: reduced same-family configs, one forward
+ one train step + one decode step on CPU, asserting shapes and finiteness.

The FULL configs are exercised only via the dry-run (ShapeDtypeStruct, no
allocation); these reduced variants keep every family's code path covered
in seconds."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.steps import make_train_step
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init

B, S = 2, 32


def reduced(arch_id):
    return get_config(arch_id).reduced()


def make_batch(cfg, rng, train=True):
    N = cfg.train_microbatches if train else 1
    lead = (N, B) if N > 1 else (B,)
    ks = jax.random.split(rng, 3)
    if cfg.frontend == "audio_codes":
        codes = jax.random.randint(ks[0], (*lead, S, cfg.n_codebooks), 0, cfg.vocab)
        batch = {"codes": codes}
        if train:
            batch["labels"] = jax.random.randint(ks[1], (*lead, S, cfg.n_codebooks),
                                                 0, cfg.vocab)
    elif cfg.frontend == "vision_embeds":
        emb = jax.random.normal(ks[0], (*lead, S, cfg.d_model), dtype=jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
        if N > 1:
            pos = jnp.broadcast_to(pos[None], (N, 3, B, S))
        batch = {"embeds": emb, "positions": pos}
        if train:
            batch["labels"] = jax.random.randint(ks[1], (*lead, S), 0, cfg.vocab)
    else:
        batch = {"tokens": jax.random.randint(ks[0], (*lead, S), 0, cfg.vocab)}
        if train:
            batch["labels"] = jax.random.randint(ks[1], (*lead, S), 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_finite(self, arch_id):
        cfg = reduced(arch_id)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg, jax.random.PRNGKey(1), train=False)
        logits, _ = T.forward(cfg, params, batch)
        Vp = cfg.vocab_padded
        want = (B, S, cfg.n_codebooks, Vp) if cfg.n_codebooks else (B, S, Vp)
        assert logits.shape == want
        real = logits[..., : cfg.vocab]
        assert bool(jnp.all(jnp.isfinite(real)))
        if Vp > cfg.vocab:  # padded slots masked, never win argmax
            assert bool(jnp.all(logits[..., cfg.vocab:] < -1e29))

    def test_train_step_decreases_nothing_nan(self, arch_id):
        cfg = reduced(arch_id)
        # keep the reduced smoke microbatched iff the real config is
        cfg = dataclasses.replace(
            cfg, train_microbatches=min(2, get_config(arch_id).train_microbatches))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
        batch = make_batch(cfg, jax.random.PRNGKey(1), train=True)
        params, opt, metrics = step(params, opt, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        # one more step must also be finite (state threading works)
        batch2 = make_batch(cfg, jax.random.PRNGKey(2), train=True)
        params, opt, metrics2 = step(params, opt, batch2)
        assert bool(jnp.isfinite(metrics2["loss"]))

    def test_decode_step(self, arch_id):
        cfg = reduced(arch_id)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        cache = T.init_cache(cfg, B, S, dtype=jnp.float32)
        if cfg.frontend == "audio_codes":
            inp = {"codes": jnp.zeros((B, 1, cfg.n_codebooks), jnp.int32),
                   "cur_index": jnp.int32(0)}
        elif cfg.frontend == "vision_embeds":
            inp = {"embeds": jnp.zeros((B, 1, cfg.d_model)),
                   "positions": jnp.zeros((3, B, 1), jnp.int32),
                   "cur_index": jnp.int32(0)}
        else:
            inp = {"tokens": jnp.zeros((B, 1), jnp.int32), "cur_index": jnp.int32(0)}
        logits, new_cache = T.serve_step(cfg, params, inp, cache)
        assert logits.shape[:2] == (B, 1)
        assert bool(jnp.all(jnp.isfinite(logits)))
        assert jax.tree.structure(new_cache) == jax.tree.structure(cache)

    def test_full_config_matches_assignment(self, arch_id):
        """The full (non-reduced) config carries the published dims."""
        cfg = get_config(arch_id)
        published = {
            "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
            "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
            "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
            "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
            "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
            "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
            "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
            "granite-34b": (88, 6144, 48, 1, 24576, 49152),
            "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
            "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
            "deepseek-v3": (61, 7168, 128, 128, 18432, 129280),
            "gigachat3.5-432b-a28b": (40, 7168, 64, 64, 18432, 128256),
            "glm-5.2": (78, 6144, 64, 64, 12288, 154880),
        }[arch_id]
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == published
