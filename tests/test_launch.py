"""Unit tests for the fleet launcher's plumbing (train/launch.py) — env contract assembly,
flag rewriting, CLI parsing — without spawning fleets (those run in test_multiprocess.py)."""

import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.train import launch as L


class TestChildEnv:
    def test_rendezvous_env_contract(self):
        env = L._child_env({}, port=12345, num_processes=4, process_id=2,
                           platform=None, devices_per_process=1,
                           tpu_ports=(1, 2, 3, 4))
        assert env["JAX_COORDINATOR_ADDRESS"] == "localhost:12345"
        assert env["JAX_NUM_PROCESSES"] == "4"
        assert env["JAX_PROCESS_ID"] == "2"
        assert "JAX_PLATFORMS" not in env

    def test_cpu_platform_sets_device_count(self):
        env = L._child_env({}, port=1, num_processes=2, process_id=0,
                           platform="cpu", devices_per_process=3)
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=3"

    def test_inherited_device_count_is_replaced(self):
        base = {"XLA_FLAGS": "--foo --xla_force_host_platform_device_count=8 --bar",
                "JAX_PLATFORMS": "cpu"}
        env = L._child_env(base, port=1, num_processes=2, process_id=1,
                           platform=None, devices_per_process=2)
        assert "device_count=8" not in env["XLA_FLAGS"]
        assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
        assert "--foo" in env["XLA_FLAGS"] and "--bar" in env["XLA_FLAGS"]

    def test_non_cpu_platform_keeps_flags(self):
        base = {"XLA_FLAGS": "--keep-me"}
        env = L._child_env(base, port=1, num_processes=1, process_id=0,
                           platform="tpu", devices_per_process=4)
        assert env["XLA_FLAGS"] == "--keep-me"
        # One process owns every chip: nothing to divide, no libtpu bounds set.
        assert not [k for k in env if k.startswith("TPU_")]

    def test_accelerator_children_each_get_their_own_chip(self):
        """Four processes on the four-chip host (PR 21): libtpu's process-bounds
        variables give child i chip i and its own port in one shared address list —
        without them every child opens all four chips and fails in backend init."""
        ports = (9001, 9002, 9003, 9004)
        envs = [L._child_env({"XLA_FLAGS": "--keep-me"}, port=1, num_processes=4,
                             process_id=i, platform=None, devices_per_process=1,
                             tpu_ports=ports) for i in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
        for e in envs:
            assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_PROCESS_ADDRESSES"] == ",".join(
                f"localhost:{p}" for p in ports)
            assert e["XLA_FLAGS"] == "--keep-me" and "JAX_PLATFORMS" not in e

    @pytest.mark.parametrize("n,per", [(2, 1), (3, 1), (4, 2), (8, 1)])
    def test_undividable_accelerator_layout_is_refused_with_a_reason(self, n, per):
        with pytest.raises(ValueError, match="chip belongs to one process"):
            L._child_env({}, port=1, num_processes=n, process_id=0, platform="tpu",
                         devices_per_process=per, tpu_ports=tuple(range(n)))

    def test_refused_layout_spawns_nothing(self, monkeypatch):
        """The refusal happens at launch, for the whole fleet, before the first
        child exists — not inside some child's backend init."""
        monkeypatch.setattr(L.subprocess, "Popen",
                            lambda *a, **k: pytest.fail("spawned a child"))
        with pytest.raises(ValueError, match="--platform cpu"):
            L.Fleet(["-c", "pass"], num_processes=2, platform="tpu")


class TestCli:
    def test_no_command_errors(self, capsys):
        with pytest.raises(SystemExit) as e:
            L.main(["--num-processes", "2"])
        assert e.value.code == 2

    def test_remainder_after_double_dash(self, monkeypatch):
        seen = {}

        def fake_launch(command, **kwargs):
            seen["command"] = command
            seen.update(kwargs)
            return 0

        monkeypatch.setattr(L, "launch", fake_launch)
        assert L.main(["--num-processes", "3", "--platform", "cpu", "--timeout", "9",
                       "--", "-m", "somemod", "--flag"]) == 0
        assert seen["command"] == ["-m", "somemod", "--flag"]
        assert seen["num_processes"] == 3
        assert seen["platform"] == "cpu"
        assert seen["timeout"] == 9.0


def test_free_port_is_bindable():
    import socket

    port = L._free_port()
    with socket.socket() as s:
        s.bind(("localhost", port))   # free at allocation time
