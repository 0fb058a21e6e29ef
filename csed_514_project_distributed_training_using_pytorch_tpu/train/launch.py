"""Local multi-process fleet launcher — run one command as N rendezvous'd processes.

The reference launches its fleet by hand: SSH into each VM, run a per-machine file whose
source encodes the rank (``src/run1.py:31`` vs ``src/run2.py:31``) or pass ``--local_rank``
to ``src/train_dist.py:121``, with the coordinator IP hardcoded in the program
(``src/train_dist.py:144``). Here the launch contract is: **every process runs the same
command**; its cluster coordinates arrive via environment (``JAX_COORDINATOR_ADDRESS``,
``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``), which ``parallel.mesh.initialize_cluster`` reads.
On a real TPU pod none of this is needed — slice metadata supplies everything — so this
launcher's jobs are (a) multi-host *emulation* on one machine (N processes × M virtual CPU
devices each — the fake-backend analog, SURVEY.md §4), (b) N processes on ONE multi-chip
host, each given its own chip(s) through libtpu's process-bounds variables (a chip belongs
to one process; a layout the launcher cannot divide is refused before anything spawns), and
(c) documenting the env contract a non-TPU fleet runner must provide.

Usage (≙ running run1.py and run2.py on two VMs, but one command, no editing)::

    python -m csed_514_project_distributed_training_using_pytorch_tpu.train.launch \
        --num-processes 2 -- \
        -m csed_514_project_distributed_training_using_pytorch_tpu.train.smoke

Everything after ``--`` is passed to ``python`` in each process. Exit status is 0 iff every
process exits 0. Under ``--fail-fast`` (the default) the first nonzero child exit SIGTERMs
the rest of the fleet immediately — peers blocked on a dead partner's rendezvous or
collective are torn down, not waited out (the clean-abort behavior the reference's
all-or-nothing gloo world lacks, SURVEY.md §5 "failure detection"); ``--no-fail-fast``
restores let-them-finish semantics (every child runs to its own exit; the first nonzero
code is still reported). The :class:`Fleet` handle this module is built on is also the
unit ``resilience/supervisor.py`` watches and restarts — this file stays jax-free so
supervisors importing it never touch the accelerator.
"""

from __future__ import annotations

import argparse
import os
import re
import socket
import subprocess
import sys
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


# How one host's chips divide among co-hosted processes, in libtpu's own variables:
# (processes, chips per process) -> (TPU_PROCESS_BOUNDS, TPU_CHIPS_PER_PROCESS_BOUNDS).
# The row is the four-chip 2x2 v5e host this launcher was run on (PR 21), divided the
# way jax's own multi-process TPU test harness divides it; a layout not listed is
# refused at launch, because its children would each open every chip and fail (or
# hang) inside backend init.
_TPU_HOST_LAYOUTS = {
    (4, 1): ("2,2,1", "1,1,1"),
}


def _child_env(base: dict, *, port: int, num_processes: int, process_id: int,
               platform: str | None, devices_per_process: int,
               tpu_ports: tuple[int, ...] = ()) -> dict:
    """Environment of child ``process_id``: the rendezvous triple, the platform, and
    the child's own devices — a virtual device count on CPU, its own chip(s) on an
    accelerator (``tpu_ports``: one free local port per process for libtpu's
    process mesh). Raises ``ValueError`` for a multi-process accelerator launch
    whose chip division is not in ``_TPU_HOST_LAYOUTS``."""
    env = dict(base)
    env["JAX_COORDINATOR_ADDRESS"] = f"localhost:{port}"
    env["JAX_NUM_PROCESSES"] = str(num_processes)
    env["JAX_PROCESS_ID"] = str(process_id)
    if platform:
        env["JAX_PLATFORMS"] = platform
    if env.get("JAX_PLATFORMS") == "cpu":
        # Each emulated host owns its own virtual device set; replace any inherited count.
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices_per_process}"
        ).strip()
    elif num_processes > 1:
        layout = _TPU_HOST_LAYOUTS.get((num_processes, devices_per_process))
        if layout is None:
            known = ", ".join(f"{n} x {d}" for n, d in sorted(_TPU_HOST_LAYOUTS))
            raise ValueError(
                f"cannot give {num_processes} processes {devices_per_process} chip(s) "
                f"each on this host: a chip belongs to one process, and the layouts "
                f"this launcher knows how to divide are (processes x chips): {known}. "
                f"Run one process over all chips, or pass --platform cpu for "
                f"multi-process emulation")
        first = process_id * devices_per_process
        env["TPU_PROCESS_BOUNDS"], env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = layout
        env["TPU_VISIBLE_CHIPS"] = ",".join(
            str(first + k) for k in range(devices_per_process))
        env["TPU_PROCESS_ADDRESSES"] = ",".join(f"localhost:{p}" for p in tpu_ports)
        env["TPU_PROCESS_PORT"] = str(tpu_ports[process_id])
        env["CLOUD_TPU_TASK_ID"] = str(process_id)
        env["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    return env


class Fleet:
    """A running fleet as one supervisable unit: spawn, poll, signal, teardown.

    ``launch()`` drives one for the simple run-to-completion case; the resilience
    supervisor holds one across its watch loop (heartbeat staleness checks, SIGTERM
    forwarding) — both get identical spawn env and teardown semantics because there
    is exactly one implementation of each."""

    def __init__(self, command: list[str], *, num_processes: int,
                 platform: str | None = None, devices_per_process: int = 1,
                 port: int | None = None, env: dict | None = None,
                 process_id_base: int = 0):
        """``process_id_base`` offsets the children's ``JAX_PROCESS_ID``: the
        serving router runs one single-process Fleet PER replica (so replicas
        crash, restart, and get supervised independently), and the offset keeps
        each replica's fleet-wide identity — heartbeat file index, fault-spec
        ``proc=`` matching — intact even though every such fleet is size 1.
        Rendezvous'd multi-process fleets keep the default 0 (a nonzero base
        would break ``initialize_cluster``'s contiguous-rank contract)."""
        self.port = port or _free_port()
        base = dict(os.environ if env is None else env)
        tpu_ports = [_free_port() for _ in range(num_processes)]
        # Every child's env is built BEFORE the first spawn: a refused chip layout
        # must not leave half a fleet running.
        envs = [_child_env(base, port=self.port, num_processes=num_processes,
                           process_id=process_id_base + i, platform=platform,
                           devices_per_process=devices_per_process,
                           tpu_ports=tpu_ports)
                for i in range(num_processes)]
        self.procs = [subprocess.Popen([sys.executable, *command], env=e)
                      for e in envs]
        self._first_failure: int | None = None

    def poll(self) -> int | None:
        """Reap finished children; return the first nonzero exit code observed so far
        (sticky), or None while none has failed."""
        for p in self.procs:
            rc = p.poll()
            if rc is not None and rc != 0 and self._first_failure is None:
                self._first_failure = rc
        return self._first_failure

    @property
    def running(self) -> bool:
        return any(p.poll() is None for p in self.procs)

    @property
    def exit_codes(self) -> list[int | None]:
        return [p.poll() for p in self.procs]

    def send_signal(self, sig) -> None:
        """Deliver ``sig`` to every live child (e.g. forwarding a preemption SIGTERM)."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except (ProcessLookupError, OSError):
                    pass

    def terminate(self, grace: float = 10.0) -> None:
        """SIGTERM every live child, give the fleet ``grace`` seconds collectively to
        exit (a cooperative preemption stop may need it), then SIGKILL stragglers and
        reap everything — a hung or failed peer must not leave zombies behind."""
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + grace
        for p in self.procs:
            try:
                p.wait(timeout=max(0.01, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def launch(command: list[str], *, num_processes: int, platform: str | None = None,
           devices_per_process: int = 1, port: int | None = None,
           timeout: float | None = None, fail_fast: bool = True) -> int:
    """Spawn ``python <command>`` ``num_processes`` times with rendezvous env; returns the
    first nonzero child exit code, else 0. Output streams through inherited stdout/stderr
    (process-0 gating in ``utils.metrics.log`` keeps it single-voiced).

    ``fail_fast`` (default): the first nonzero exit tears the fleet down immediately —
    peers blocked on a dead partner's rendezvous/collective get terminated rather than
    waited out. ``fail_fast=False`` lets every child run to its own exit first. Either
    way a shared ``timeout`` deadline bounds total wall time (exit 124, the coreutils
    ``timeout`` convention)."""
    fleet = Fleet(command, num_processes=num_processes, platform=platform,
                  devices_per_process=devices_per_process, port=port)
    deadline = None if timeout is None else time.monotonic() + timeout
    result: int | None = None
    try:
        while fleet.running:
            rc = fleet.poll()
            if rc is not None and fail_fast:
                result = rc
                break
            if deadline is not None and time.monotonic() > deadline:
                result = 124
                break
            time.sleep(0.05)
        if result is None:       # clean drain, or --no-fail-fast ran everyone to exit
            result = fleet.poll()
    finally:
        fleet.terminate()
    return result or 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n", 1)[0],
        usage="python -m ....train.launch --num-processes N [options] -- <python args>")
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform in children (e.g. cpu for emulation)")
    parser.add_argument("--devices-per-process", type=int, default=1,
                        help="devices each process owns: virtual devices per emulated "
                             "host on cpu, chips per process on an accelerator")
    parser.add_argument("--port", type=int, default=None,
                        help="coordinator port (default: pick a free one)")
    parser.add_argument("--timeout", type=float, default=None,
                        help="wall-clock seconds before the whole fleet is killed "
                             "(exit 124); default: wait forever")
    parser.add_argument("--fail-fast", action=argparse.BooleanOptionalAction,
                        default=True,
                        help="SIGTERM the rest of the fleet the moment any child "
                             "exits nonzero (peers hung on dead collectives are torn "
                             "down, not waited out); --no-fail-fast lets every child "
                             "run to its own exit")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="everything after -- is run as: python <command>")
    args = parser.parse_args(argv)
    command = args.command[1:] if args.command[:1] == ["--"] else args.command
    if not command:
        parser.error("no command given — pass e.g. `-- -m <module> [args]`")
    return launch(command, num_processes=args.num_processes, platform=args.platform,
                  devices_per_process=args.devices_per_process, port=args.port,
                  timeout=args.timeout, fail_fast=args.fail_fast)


if __name__ == "__main__":
    raise SystemExit(main())
