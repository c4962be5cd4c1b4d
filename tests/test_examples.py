"""Examples as smoke tests (reference CI pattern: examples run under
mpirun/horovodrun in the Buildkite pipeline, gen-pipeline.sh:127-168)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run(cmd, extra_env=None, timeout=300, virtual_mesh=False):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if virtual_mesh:  # the standard 8-device CPU mesh recipe
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.update(extra_env or {})
    rv = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=timeout, cwd=REPO)
    assert rv.returncode == 0, rv.stdout + "\n" + rv.stderr
    return rv.stdout


def test_jax_mnist_example():
    out = _run([sys.executable, "examples/jax_mnist.py"],
               virtual_mesh=True)
    assert "done" in out


def test_pytorch_mnist_example_under_hvdrun():
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, "examples/pytorch_mnist.py"])


@pytest.mark.slow  # ~80 s CPU: full slope-window bench subprocess
def test_synthetic_benchmark_tiny():
    out = _run([sys.executable, "examples/jax_synthetic_benchmark.py",
                "--model", "resnet18", "--batch-size", "2",
                "--image-size", "32", "--num-warmup-batches", "1",
                "--num-batches-per-iter", "2", "--num-iters", "2"],
               virtual_mesh=True)
    assert "Img/sec per chip" in out


def test_elastic_train_example(tmp_path):
    """The elastic example (ISSUE 1): commit/restore under
    @hvd.elastic.run, CPU-safe, resumes from the committed step when
    re-run after an interruption."""
    env = {"ELASTIC_CKPT_DIR": str(tmp_path / "ck")}
    out = _run([sys.executable, "examples/elastic_train.py"],
               extra_env=env, virtual_mesh=True)
    assert "done at step 30" in out
    # second run starts from the final committed step: no retraining
    out2 = _run([sys.executable, "examples/elastic_train.py"],
                extra_env=env, virtual_mesh=True)
    assert "done at step 30" in out2
    assert "step   1" not in out2


def test_imagenet_resnet50_example_under_hvdrun(tmp_path):
    """The real-data flagship example (reference:
    pytorch_imagenet_resnet50.py): per-rank disjoint sharding via
    DistributedSampler, fused eager gradient averaging, rank-0
    checkpointing + broadcast resume — at smoke scale with the
    synthetic-data fallback."""
    ckpt = str(tmp_path / "ck")
    smoke = ["--depth", "18", "--num-filters", "4", "--image-size", "32",
             "--num-classes", "4", "--num-examples", "16",
             "--batch-size", "2", "--ckpt-dir", ckpt]
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, "examples/jax_imagenet_resnet50.py",
                "--epochs", "1"] + smoke)
    # each rank sees 8 of 16 examples; together a full epoch
    assert "(16 examples/epoch across 2 ranks)" in out
    assert "epoch 1" in out
    # resume leg: restores epoch 1, runs epoch 2
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, "examples/jax_imagenet_resnet50.py",
                "--epochs", "2"] + smoke)
    assert "resuming from epoch 1" in out and "epoch 2" in out


def test_checkpoint_resume_example(tmp_path):
    ckpt = str(tmp_path / "ck")
    # first leg: 4 epochs
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, "examples/jax_checkpoint_resume.py",
                "--ckpt-dir", ckpt, "--epochs", "4"])
    assert "epoch 4" in out
    # second leg resumes at 4 and finishes
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                sys.executable, "examples/jax_checkpoint_resume.py",
                "--ckpt-dir", ckpt, "--epochs", "8"])
    assert "resuming from step 4" in out and "epoch 8" in out


def test_lm_seq_parallel_example():
    out = _run([sys.executable, "examples/jax_lm_seq_parallel.py",
                "--steps", "15", "--seq-len", "128"],
               virtual_mesh=True)
    assert "data x seq" in out


def test_lm_tensor_parallel_example():
    out = _run([sys.executable, "examples/jax_lm_tensor_parallel.py",
                "--steps", "6", "--d-model", "32", "--seq-len", "32"],
               virtual_mesh=True)
    assert "d_ff kernel sharding: PartitionSpec(None, 'model')" in out
    assert "done" in out


def test_lm_moe_example():
    out = _run([sys.executable, "examples/jax_lm_moe.py",
                "--steps", "6", "--d-model", "32", "--seq-len", "32"],
               virtual_mesh=True)
    assert "w_in sharding: PartitionSpec('expert'" in out
    assert "done" in out


@pytest.mark.slow  # ~80 s CPU: weak-scaling sweep subprocess
def test_scaling_harness_tiny():
    out = _run([sys.executable, "bench_scaling.py", "--model", "resnet18",
                "--batch-size", "2", "--image-size", "32",
                "--num-warmup", "1", "--num-iters", "2"],
               virtual_mesh=True)
    assert "weak_scaling_efficiency" in out


def test_hierarchical_example():
    out = _run([sys.executable, "examples/jax_hierarchical_allreduce.py",
                "--steps", "3"],
               virtual_mesh=True)
    assert "reduce-scatter" in out and "done" in out


def test_lm_benchmark_tiny():
    out = _run([sys.executable, "examples/jax_lm_benchmark.py",
                "--data", "2", "--seq", "4", "--steps", "2", "--warmup", "1",
                "--layers", "2", "--d-model", "64", "--heads", "4",
                "--vocab", "128", "--seq-len", "512", "--batch", "4"],
               virtual_mesh=True)
    assert "transformer_lm_tokens_per_sec" in out


def _has_module(name):
    import importlib.machinery
    try:
        return importlib.machinery.PathFinder.find_spec(name) is not None
    except (ImportError, ValueError):
        return False


def test_tf_keras_mnist_example_under_hvdrun():
    """The reference's tensorflow2_keras_mnist CI smoke: 2 processes
    under hvdrun, DistributedOptimizer + callbacks + rank-0 checkpoint
    (reference gen-pipeline.sh:127-168 example-run pattern)."""
    import pytest
    if not _has_module("tensorflow"):
        pytest.skip("tensorflow not installed")
    import tempfile
    ckpt_dir = tempfile.mkdtemp()
    env = {"TF_CPP_MIN_LOG_LEVEL": "3", "CKPT_DIR": ckpt_dir}
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                "-H", "localhost:2", sys.executable,
                "examples/tensorflow2_keras_mnist.py", "--epochs", "1",
                "--samples", "64"], extra_env=env, timeout=600)
    assert out.count("done") == 2
    assert "checkpoints: ['ckpt-1.keras']" in out
    # resume conventions: a second run against the same CKPT_DIR must
    # discover epoch 1, broadcast it, and continue to epoch 2
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                "-H", "localhost:2", sys.executable,
                "examples/tensorflow2_keras_mnist.py", "--epochs", "2",
                "--samples", "64"], extra_env=env, timeout=600)
    assert out.count("done") == 2
    assert "resuming from epoch 1" in out
    assert "'ckpt-2.keras'" in out


def test_mxnet_mnist_example_under_hvdrun():
    """The reference's mxnet_mnist CI smoke (runs in the real-mxnet CI
    job; skipped where mxnet has no wheel, e.g. this py3.12 image)."""
    import pytest
    if not _has_module("mxnet"):
        pytest.skip("mxnet not installed")
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                "-H", "localhost:2", sys.executable,
                "examples/mxnet_mnist.py", "--epochs", "1",
                "--samples", "64"], timeout=600)
    assert out.count("done") == 2


def test_tf2_custom_loop_example_under_hvdrun():
    """The reference's tensorflow2_mnist CI smoke: custom GradientTape
    loop with DistributedGradientTape, post-step-1 variable broadcast,
    rank-0 checkpoint, weight-digest sync proof."""
    import pytest
    if not _has_module("tensorflow"):
        pytest.skip("tensorflow not installed")
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                "-H", "localhost:2", sys.executable,
                "examples/tensorflow2_mnist.py", "--steps", "12"],
               extra_env={"TF_CPP_MIN_LOG_LEVEL": "3"}, timeout=600)
    assert out.count("done") == 2
    assert "checkpoint: model.weights.h5" in out


def test_pytorch_synthetic_benchmark_under_hvdrun():
    """The reference's pytorch_synthetic_benchmark CI smoke, 2-proc on
    the host plane."""
    out = _run([sys.executable, "-m", "horovod_tpu.run", "-np", "2",
                "-H", "localhost:2", sys.executable,
                "examples/pytorch_synthetic_benchmark.py",
                "--num-iters", "2", "--num-batches-per-iter", "3"],
               timeout=600)
    assert out.count("done") == 2
    assert "Total img/sec on 2 processes" in out
