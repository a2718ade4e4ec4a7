"""JaxTrainer end-to-end (modeled on reference python/ray/train/tests/
test_data_parallel_trainer.py): real cluster, real worker actors, real jax."""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    FailureConfig,
    JaxConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


@pytest.fixture
def ray_4cpu(tmp_path):
    ray_tpu.init(num_cpus=4)
    yield str(tmp_path)
    ray_tpu.shutdown()


@pytest.mark.parametrize("environ, want", [
    ({"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}, None),  # jax's own setting
    ({"JAX_PLATFORMS": "cpu"}, None),                          # held to the CPU
    ({"JAX_PLATFORMS": "tpu,cpu"}, ".jax_cache"),
    ({}, ".jax_cache"),
])
def test_worker_compile_cache_default_is_one_fixed_path(environ, want):
    from ray_tpu._private import repo_root
    from ray_tpu.train._worker_group import _default_compile_cache_dir

    got = _default_compile_cache_dir(environ)
    assert got == (want and os.path.join(repo_root(), want))
    assert _default_compile_cache_dir(environ) == got  # no pid, time or temp part


def test_report_rounds_and_context(ray_4cpu):
    def loop(config):
        ctx = train.get_context()
        assert ctx.get_world_size() == 2
        for step in range(3):
            train.report({"step": step, "rank": ctx.get_world_rank(),
                          "lr": config["lr"]})

    trainer = JaxTrainer(
        loop,
        train_loop_config={"lr": 0.1},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=ray_4cpu, name="ctx"),
        jax_config=JaxConfig(distributed=False),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 2
    assert result.metrics["rank"] == 0  # rank-0 metrics win
    assert len(result.metrics_history) == 3


def test_checkpoint_save_and_restore(ray_4cpu):
    def loop(config):
        import json

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "state.json")) as f:
                start = json.load(f)["step"] + 1
        for step in range(start, start + 2):
            if ctx.get_world_rank() == 0:
                import tempfile

                d = tempfile.mkdtemp()
                with open(os.path.join(d, "state.json"), "w") as f:
                    json.dump({"step": step}, f)
                train.report({"step": step}, checkpoint=Checkpoint(d))
            else:
                train.report({"step": step})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            storage_path=ray_4cpu, name="ckpt",
            checkpoint_config=CheckpointConfig(num_to_keep=2),
        ),
        jax_config=JaxConfig(distributed=False),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 1
    assert result.checkpoint is not None

    # resume: picks up where the checkpoint left off
    trainer2 = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=ray_4cpu, name="ckpt2"),
        jax_config=JaxConfig(distributed=False),
        resume_from_checkpoint=result.checkpoint,
    )
    result2 = trainer2.fit()
    assert result2.metrics["step"] == 3


def test_worker_error_surfaces(ray_4cpu):
    def loop(config):
        ctx = train.get_context()
        train.report({"ok": True})
        if ctx.get_world_rank() == 1:
            raise ValueError("boom at rank 1")
        train.report({"ok": True})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=ray_4cpu, name="err"),
        jax_config=JaxConfig(distributed=False),
    )
    with pytest.raises(train.TrainingFailedError, match="boom at rank 1"):
        trainer.fit()


def test_jax_distributed_spmd_training(ray_4cpu):
    """2 worker processes x 4 virtual CPU devices = one 8-device dp mesh;
    the sharded GPT-2 step must train with per-process batch shards."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.gpt2 import GPT2Config
        from ray_tpu.parallel.mesh import make_mesh
        from ray_tpu.parallel.train_step import TrainStep

        assert jax.process_count() == 2
        assert len(jax.devices()) == 8

        cfg = GPT2Config.tiny(use_flash_attention=False, dtype=jnp.float32)
        mesh = make_mesh({"dp": 8})
        ts = TrainStep(cfg, mesh, learning_rate=1e-3)
        state = ts.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(train.get_context().get_world_rank())
        B_local, T = 4, 32
        for _ in range(2):
            idx = rng.integers(0, cfg.vocab_size, (B_local, T)).astype(np.int32)
            batch_local = {
                "idx": idx, "targets": np.roll(idx, -1, axis=1),
            }
            batch = jax.make_array_from_process_local_data(
                ts.batch_sharding,
                batch_local["idx"],
            )
            tgt = jax.make_array_from_process_local_data(
                ts.batch_sharding,
                batch_local["targets"],
            )
            state, m = ts.step(state, {"idx": batch, "targets": tgt})
        train.report({"loss": float(m["loss"])})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=ray_4cpu, name="spmd"),
        jax_config=JaxConfig(
            distributed=True,
            env={
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            },
        ),
    )
    result = trainer.fit()
    assert np.isfinite(result.metrics["loss"])


def test_group_restart_on_failure(ray_4cpu):
    marker = os.path.join(ray_4cpu, "died_once")

    def loop(config):
        import json
        import tempfile

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "s.json")) as f:
                start = json.load(f)["step"] + 1
        for step in range(start, 4):
            if ctx.get_world_rank() == 0:
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "s.json"), "w") as f:
                    json.dump({"step": step}, f)
                train.report({"step": step}, checkpoint=Checkpoint(d))
            else:
                train.report({"step": step})
            if step == 1 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                os._exit(1)  # hard-kill the worker process

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            storage_path=ray_4cpu, name="restart",
            failure_config=FailureConfig(max_failures=1),
        ),
        jax_config=JaxConfig(distributed=False),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 3


def test_pipelined_checkpoint_report_blocks_until_ack():
    """With pipeline_depth > 1, a checkpoint report must not return before
    the driver acked it (the checkpoint dir may be deleted right after
    report() returns — reference train/_internal/session.py:667 persists
    before returning). Metrics-only reports stay pipelined."""
    import threading
    import time

    from ray_tpu.train._session import TrainContext, _Session

    ctx = TrainContext(0, 1, 0, 1, "127.0.0.1")
    s = _Session(ctx, None, pipeline_depth=8)

    # metrics-only reports return immediately (no ack yet)
    for i in range(4):
        s.report({"step": i}, None)

    state = {"returned": False}

    def ckpt_report():
        s.report({"step": 4}, Checkpoint("/tmp"))
        state["returned"] = True

    t = threading.Thread(target=ckpt_report, daemon=True)
    t.start()
    time.sleep(0.2)
    assert not state["returned"], "checkpoint report returned before ack"
    # driver consumes+acks the first 4 rounds: still not this report's turn
    s.ack(4)
    time.sleep(0.2)
    assert not state["returned"]
    s.ack(1)  # ack the checkpoint round itself
    t.join(timeout=5)
    assert state["returned"]
