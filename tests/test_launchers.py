"""CLI launcher smoke tests (train/serve) + vocab padding + zigzag-in-model
coverage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.configs.base import RunConfig
from repro.models import build_model


def test_train_cli_runs_and_learns():
    from repro.launch.train import main
    report = main(["--arch", "chatglm3-6b", "--reduced", "--steps", "25",
                   "--batch", "8", "--seq", "32", "--lr", "1e-2"])
    assert report.final_step == 25
    assert np.mean(report.losses[-3:]) < np.mean(report.losses[:3])


def test_serve_cli_runs():
    from repro.launch.serve import main
    done = main(["--arch", "deepseek-7b", "--reduced", "--requests", "3",
                 "--slots", "2", "--max-new", "4"])
    assert len(done) == 3
    assert all(len(r.out_tokens) == 4 for r in done)


def test_vocab_padding_whisper():
    """whisper's 51865 vocab pads to a 128-multiple; padded columns are
    masked to -inf so they can never be sampled; CE ignores them."""
    cfg = get_arch("whisper-small").reduced(vocab_size=131)  # not 128-mult
    run = RunConfig(attn_impl="full", remat="nothing",
                    compute_dtype="float32")
    m = build_model(cfg, run)
    assert m.padded_vocab == 256
    p = m.init(jax.random.PRNGKey(0))
    assert p["embed"].shape[0] == 256
    B, S = 2, 8
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 131),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 131),
        "frames": 0.1 * jnp.ones((B, cfg.encdec.enc_len, cfg.d_model)),
    }
    lg, _ = m.forward(p, batch)
    assert lg.shape[-1] == 256
    assert bool(jnp.all(lg[..., 131:] < -1e20))       # masked
    assert bool(jnp.all(jnp.argmax(lg, -1) < 131))    # never sampled
    loss, _ = m.loss_fn(p, batch)
    assert np.isfinite(float(loss))


def test_zigzag_model_path_matches_blocked():
    """attn_impl='zigzag' through the full model == 'blocked'."""
    cfg = get_arch("deepseek-7b").reduced()
    base = RunConfig(remat="nothing", compute_dtype="float32",
                     attn_block_q=8, attn_block_kv=8)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                              cfg.vocab_size)
    outs = {}
    for impl in ("blocked", "zigzag"):
        m = build_model(cfg, base.with_(attn_impl=impl))
        p = m.init(jax.random.PRNGKey(0))
        outs[impl], _ = m.forward(p, {"tokens": toks})
    np.testing.assert_allclose(outs["blocked"], outs["zigzag"],
                               atol=2e-4, rtol=2e-4)


def test_presets_cover_all_cells():
    from repro.configs import SHAPES, grid
    from repro.configs.base import MeshConfig
    from repro.launch.presets import preset_run
    mc = MeshConfig((16, 16), ("data", "model"))
    for cfg, shape in grid():
        run = preset_run(cfg, shape, mc)
        if shape.mode == "train":
            assert run.microbatches >= 1
            assert shape.global_batch % (run.microbatches) == 0
        else:
            assert run.microbatches == 1
