"""Fault tolerance: restartable runner (bit-exact recovery from injected
failures), straggler monitor, elastic mesh planning."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.archs import get_arch
from repro.configs.base import RunConfig, ShapeConfig
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.distributed.steps import init_train_state, make_train_step
from repro.ft.elastic import plan_mesh_shape
from repro.ft.monitor import StepTimeMonitor
from repro.ft.runner import ResilientTrainer, RunnerConfig
from repro.launch.mesh import make_host_mesh


def _trainer(tmp_path, fail_at=(), steps=8, sub="a"):
    arch = get_arch("llama3.2-1b", smoke=True)
    shape = ShapeConfig("t", 32, 4, "train")
    mesh = make_host_mesh(model_parallel=1)
    run = RunConfig(mesh_model_parallel=1, learning_rate=3e-2)  # fast smoke descent
    with jax.set_mesh(mesh):
        bundle = make_train_step(arch, run, shape, mesh)
        state = init_train_state(bundle)
        pipeline = SyntheticLMPipeline(arch, shape, PipelineConfig(seed=0))
        trainer = ResilientTrainer(
            step_fn=bundle.jit(),
            state=state,
            pipeline=pipeline,
            ckpt=CheckpointManager(tmp_path / sub, keep_n=10, async_save=False),
            cfg=RunnerConfig(total_steps=steps, checkpoint_every=2),
            fail_at=fail_at,
        )
    return trainer, mesh


def test_recovery_is_bit_exact(tmp_path):
    """A run with two injected failures must converge to the identical final
    state as an undisturbed run (deterministic data + restore)."""
    clean, mesh = _trainer(tmp_path, fail_at=(), sub="clean")
    with jax.set_mesh(mesh):
        s_clean = clean.run()
        faulty, _ = _trainer(tmp_path, fail_at=(3, 5), sub="faulty")
        s_faulty = faulty.run()
    assert faulty.restarts == 2
    for a, b in zip(jax.tree.leaves(s_clean["params"]), jax.tree.leaves(s_faulty["params"])):
        assert jnp.array_equal(a, b), "recovery diverged from the clean run"


def test_loss_decreases_through_failures(tmp_path):
    tr, mesh = _trainer(tmp_path, fail_at=(4,), steps=10)
    with jax.set_mesh(mesh):
        tr.run()
    losses = [h["loss"] for h in tr.history]
    assert losses[-1] < losses[0]


def test_too_many_failures_raises(tmp_path):
    tr, mesh = _trainer(tmp_path, fail_at=(2, 3, 4, 5), steps=8)
    tr.cfg.max_restarts = 2
    from repro.ft.runner import FailureError

    with pytest.raises(FailureError), jax.set_mesh(mesh):
        tr.run()


def test_straggler_monitor_flags_outlier():
    mon = StepTimeMonitor(warmup_steps=3)
    flags = [mon.record(i, 0.10 + 0.001 * (i % 3)) for i in range(10)]
    assert not any(flags)
    assert mon.record(10, 1.0) is True  # 10× step time
    assert mon.record(11, 0.10) is False  # recovered; EMA not poisoned
    assert mon.stragglers == [10]


@pytest.mark.parametrize("n,expect", [
    (256, (16, 16)), (255, (8, 16)), (128, (8, 16)), (96, (4, 16)), (16, (1, 16)), (8, (1, 8)),
])
def test_elastic_mesh_planning(n, expect):
    data, model = plan_mesh_shape(n, prefer_model=16)
    assert (data, model) == expect
    assert data * model <= n


def test_elastic_respects_divisibility():
    arch = get_arch("gemma3-1b")  # d_model 1152 = 2^7 * 9 -> model <= 128? (1152/64=18) ✓ 64
    data, model = plan_mesh_shape(256, prefer_model=256, arch=arch)
    assert arch.d_model % model == 0
