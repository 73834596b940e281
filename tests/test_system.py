"""End-to-end system behaviour: train -> checkpoint -> restore -> serve, plus
a small-mesh lower+compile of the production step functions (the CI-sized
twin of the 512-device dry-run)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.core.policy import PrecisionPolicy, get_policy
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import transformer as T
from repro.optim import adamw
from repro.serve.engine import ServeEngine
from repro.train import trainer as trainer_lib


def test_train_checkpoint_serve_roundtrip(tmp_path):
    """The full lifecycle: train a real (smoke) LM on the synthetic stream,
    checkpoint, restore into a fresh process-state, serve generations."""
    cfg = get_config("paper-mpfp-100m", smoke=True)
    pipe = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=17,
                                  global_batch=4))
    tcfg = trainer_lib.TrainerConfig(
        opt=adamw.AdamWConfig(lr=3e-3), total_steps=20, warmup=2,
        ckpt_dir=str(tmp_path), ckpt_every=10)
    trainer = trainer_lib.Trainer(cfg, tcfg)
    state, hist = trainer.run(pipe, num_steps=20, log_every=0)
    assert hist[-1] < hist[0]

    # restore into a fresh trainer (simulated restart)
    t2 = trainer_lib.Trainer(cfg, tcfg)
    fresh = t2.init_state()
    restored, step = t2.maybe_restore(fresh)
    assert step == 20

    # serve from the restored params
    eng = ServeEngine(cfg, restored.params, max_batch=2, max_seq=48)
    outs = eng.generate([np.asarray([1, 2, 3], np.int32)], max_new=4)
    assert len(outs[0]) == 4
    assert all(0 <= t < cfg.vocab for t in outs[0])


def test_trained_model_beats_chance():
    """The synthetic bigram task has ~85% determinism: a trained smoke model
    must beat the uniform-chance NLL by a wide margin."""
    cfg = get_config("paper-mpfp-100m", smoke=True)
    pipe = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=17,
                                  global_batch=8))
    tcfg = trainer_lib.TrainerConfig(opt=adamw.AdamWConfig(lr=3e-3),
                                     total_steps=60, warmup=3)
    trainer = trainer_lib.Trainer(cfg, tcfg)
    _, hist = trainer.run(pipe, num_steps=60, log_every=0)
    chance = np.log(cfg.vocab)  # ~5.55
    assert hist[-1] < 0.8 * chance, hist[-1]


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 fake devices "
                           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_small_mesh_dryrun_train_and_decode():
    """CI twin of the 512-chip dry-run: lower+compile train and serve steps
    on a (2, 4) mesh with the production sharding rules."""
    import dataclasses

    from repro.configs.shapes import ShapeCell
    from repro.launch import specs as specs_lib
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(data=2, model=4)
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=8))  # 8 experts % 4
    train_cell = ShapeCell("ci_train", 32, 8, "train")
    cell = specs_lib.build_cell("lite-smoke", cfg, "train_4k", mesh) \
        if False else None
    # build manually against the CI cell
    rules = specs_lib.make_rules(mesh, train_cell, cfg)
    state_st, ocfg = specs_lib.state_structs(cfg, rules, "float32")
    tcfg = trainer_lib.TrainerConfig(opt=ocfg)
    step = trainer_lib.make_train_step(cfg, PrecisionPolicy.train_default(),
                                       tcfg, mesh=mesh)
    batch = specs_lib.batch_structs(cfg, train_cell, rules)
    batch["labels"] = specs_lib.label_struct(cfg, train_cell, rules)

    from repro.dist import sharding as sh_lib

    def fn(state, batch):
        with sh_lib.use_rules(rules):
            return step(state, batch)

    with mesh:
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(state_st,
                                                          batch).compile()
    assert compiled.cost_analysis()["flops"] > 0

    # decode step
    dec_cell = ShapeCell("ci_decode", 64, 8, "decode")
    rules_d = specs_lib.make_rules(mesh, dec_cell, cfg)
    params_st = specs_lib.params_structs(cfg, rules_d)
    cache_st = specs_lib.cache_structs(cfg, dec_cell, rules_d)
    srv = trainer_lib.make_serve_step(cfg, PrecisionPolicy.serve_default(),
                                      mesh=mesh)
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)

    def dfn(params, cache, tokens):
        with sh_lib.use_rules(rules_d):
            return srv(params, cache, tokens)

    with mesh:
        dcompiled = jax.jit(dfn, donate_argnums=(1,)).lower(
            params_st, cache_st, tok).compile()
    assert dcompiled.memory_analysis().temp_size_in_bytes >= 0


def test_auto_policy_end_to_end():
    """Mode-1 AUTO as the whole-network policy: forward must run and produce
    finite logits (lax.switch branches compile per matmul site)."""
    cfg = get_config("paper-mpfp-100m", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab,
                                                         (2, 16)), jnp.int32)
    logits, _, _ = T.forward(params, {"tokens": toks}, cfg,
                             get_policy("auto"))
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """chip_smoke.py prints no result line and exits non-zero on a host
    without a TPU."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
