"""Contract tests for the driver-graded entry points.

``dryrun_multichip(n)`` runs on this host's devices when it has ``n`` of
them; on a shortfall it runs over a forced ``n``-device CPU mesh in a child
process (a dry run needs devices, not chips) and the child's failure is the
caller's.
"""

import subprocess

import pytest

import __graft_entry__ as graft
from dragonfly2_tpu.tpu import runtime


class TestDryrunDeviceShortfall:
    def test_shortfall_runs_in_a_cpu_mesh_child(self, monkeypatch):
        calls = {}

        def fake_run(argv, env=None, **kw):
            calls["argv"], calls["env"] = argv, env
            return subprocess.CompletedProcess(argv, 0, stdout="", stderr="")

        monkeypatch.setattr(subprocess, "run", fake_run)
        monkeypatch.setattr(runtime, "bring_up", lambda: [object()])
        monkeypatch.delenv("_DF_DRYRUN_CHILD", raising=False)
        graft.dryrun_multichip(8)
        assert calls["env"]["_DF_DRYRUN_CHILD"] == "1"
        assert calls["env"]["JAX_PLATFORMS"] == "cpu"
        assert ("--xla_force_host_platform_device_count=8"
                in calls["env"]["XLA_FLAGS"])
        assert "dryrun_multichip(8)" in calls["argv"][-1]

    def test_child_failure_propagates(self, monkeypatch):
        monkeypatch.setattr(
            subprocess, "run", lambda argv, **kw: subprocess.CompletedProcess(
                argv, 3, stdout="", stderr="boom"))
        monkeypatch.setattr(runtime, "bring_up", lambda: [object()])
        monkeypatch.delenv("_DF_DRYRUN_CHILD", raising=False)
        with pytest.raises(subprocess.CalledProcessError) as ei:
            graft.dryrun_multichip(8)
        assert ei.value.returncode == 3

    def test_child_that_is_still_short_does_not_recurse(self, monkeypatch):
        monkeypatch.setattr(runtime, "bring_up", lambda: [object()])
        monkeypatch.setenv("_DF_DRYRUN_CHILD", "1")
        with pytest.raises(RuntimeError, match="did not take"):
            graft.dryrun_multichip(8)


class TestEntry:
    def test_entry_forward_compiles(self):
        import jax

        fn, args = graft.entry()
        out = jax.jit(fn)(*args)
        out.block_until_ready()
        assert out.shape[0] == 256


def test_dryrun_inline_on_virtual_mesh(capsys):
    """With 8 virtual CPU devices (conftest) the full sharded train step
    runs inline — the same path the driver grades — and says where."""
    graft.dryrun_multichip(8)
    assert "platform=cpu mesh={'dp': 4, 'tp': 2}" in capsys.readouterr().out
