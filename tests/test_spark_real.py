"""Real-pyspark smoke: ``run_on_cluster`` through the
REAL SparkBackend against a ``local[2]`` SparkContext — the same shape
the reference proves with a local SparkSession
(``/root/reference/horovod/spark/__init__.py:101-236``,
``test/test_spark.py``).

Runs in the CI job that installs pyspark; skips where pyspark is absent
(this image has no network). The stub-backed tests in
``tests/test_cluster.py`` keep in-image coverage of the same code path.
"""

import importlib.machinery

import pytest


def _has_pyspark():
    try:
        return importlib.machinery.PathFinder.find_spec(
            "pyspark") is not None
    except (ImportError, ValueError):
        return False


pytestmark = pytest.mark.skipif(not _has_pyspark(),
                                reason="pyspark not installed")


@pytest.fixture(scope="module")
def sc():
    import pyspark
    conf = pyspark.SparkConf().setMaster("local[2]").setAppName(
        "hvd-tpu-real-spark-test")
    ctx = pyspark.SparkContext(conf=conf)
    yield ctx
    ctx.stop()


def _train(value):
    """Runs in each Spark-launched worker process."""
    import horovod_tpu as hvd
    hvd.init()
    import numpy as np
    out = hvd.allreduce(np.full(4, float(hvd.rank() + 1), np.float32),
                        name="spark.ar", op="sum")
    return {"rank": hvd.rank(), "size": hvd.size(),
            "sum": out.tolist(), "value": value}


def test_run_on_cluster_through_real_spark(sc):
    from horovod_tpu.run.cluster import SparkBackend, run_on_cluster

    results = run_on_cluster(_train, args=(42,), num_proc=2,
                             backend=SparkBackend(sc))
    assert sorted(r["rank"] for r in results) == [0, 1]
    for r in results:
        assert r["size"] == 2
        assert r["value"] == 42
        assert r["sum"] == [3.0, 3.0, 3.0, 3.0]


def test_spark_failure_propagates(sc):
    from horovod_tpu.run.cluster import SparkBackend, run_on_cluster

    def boom(_):
        raise RuntimeError("intentional worker failure")

    with pytest.raises(RuntimeError):
        run_on_cluster(boom, args=(0,), num_proc=2,
                       backend=SparkBackend(sc))
