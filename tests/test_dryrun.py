"""The driver-facing dry run must PROVE parity, not just finiteness:
every parallelism section compares its step against a single-device
oracle replay. These tests pin both directions — a clean
run passes, a deliberately broken sharding fails the parity gate."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import __graft_entry__ as graft  # noqa: E402


def test_dryrun_body_2dev_passes():
    graft._dryrun_body(2)


def test_dryrun_parity_catches_broken_sharding(monkeypatch):
    """Break the hierarchical allreduce (sum where average belongs — a
    classic wrong-divisor sharding bug): the updated params diverge from
    the single-device oracle and the parity assertion must fire."""
    from horovod_tpu.parallel import hierarchical as hier

    real = hier.hierarchical_allreduce

    def broken(x, ici_axes=("data",), dcn_axis="dcn", op="average"):
        del op  # drop the divisor: gradients arrive size-times too big
        return real(x, ici_axes=ici_axes, dcn_axis=dcn_axis, op="sum")

    monkeypatch.setattr(hier, "hierarchical_allreduce", broken)
    with pytest.raises(AssertionError, match="oracle"):
        graft._dryrun_body(2)
