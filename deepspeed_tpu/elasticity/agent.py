"""In-band elastic agent: supervise training, restart on failure.

Reference: `deepspeed/elasticity/elastic_agent.py:32` `DSElasticAgent`
(subclassing torch-elastic's LocalElasticAgent) — on membership change or
worker failure the rendezvous restarts workers with the new WORLD_SIZE,
and recovery is *checkpoint-based*: the restarted job re-runs
`load_checkpoint` (universal checkpointing makes that topology-free).

TPU-native shape: there is no torch-elastic rendezvous — a training job is
one process per host over a fixed device mesh, and a chip/host failure
kills the process.  The agent is therefore a supervisor that runs the
training script as a subprocess and, on a non-zero exit:
  1. re-validates that a restart makes sense (attempts remaining; with
     min_uptime_s set, a first try that dies faster than that is treated
     as a config error and NOT retried),
  2. recomputes the elastic batch configuration for whatever world the
     restarted process will see (`compute_elastic_config` — v0.1/v0.2
     math, the same module the reference uses), exporting it via
     `DSTPU_ELASTIC_*` env vars the script can consume,
  3. restarts pointing the script at its own latest checkpoint (the
     script's normal `load_checkpoint(latest)` path — exactly the
     reference's recovery contract).

The restart counter rides `DSTPU_ELASTIC_RESTART` so the script can tell
a cold start from a resume.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from ..utils.logging import logger
from .elasticity import compute_elastic_config

__all__ = ["DSElasticAgent", "PodElasticAgent"]


def _elastic_env_vars(elastic_config: Optional[Dict], world: int,
                      restart: int, chips_per_host: int = 1
                      ) -> Dict[str, str]:
    """The DSTPU_ELASTIC_* env contract, shared by both agents so the
    exported surface cannot drift between single-process and pod
    supervision."""
    env = {"DSTPU_ELASTIC_RESTART": str(restart),
           "DSTPU_ELASTIC_WORLD": str(world)}
    if elastic_config is not None:
        batch, _worlds, micro = compute_elastic_config(
            elastic_config, world_size=world, return_microbatch=True,
            chips_per_host=chips_per_host)
        env["DSTPU_ELASTIC_BATCH"] = str(batch)
        if micro is not None:
            env["DSTPU_ELASTIC_MICRO"] = str(micro)
    return env


class DSElasticAgent:
    """Supervise `cmd` (a training-script argv); restart on failure with a
    recomputed elastic config.

    Args:
      cmd: argv of the training process (e.g. ["python", "train.py", ...]).
      elastic_config: the job config dict containing the "elasticity"
        section (reference ds_config shape); when given, each (re)start
        exports DSTPU_ELASTIC_BATCH / DSTPU_ELASTIC_MICRO so the script
        can honor the world-size-compatible batch.
      world_size_fn: () -> int, the world size the NEXT start will see;
        defaults to the current process's visible device count at restart
        time.  Injectable for tests and multi-host launchers.
      max_restarts: restarts allowed before giving up (reference
        torch-elastic max_restarts).
      restart_delay_s: pause before a restart (lets a replacement host or
        a TPU re-grant settle).
      min_uptime_s: when > 0, a FIRST attempt that exits non-zero faster
        than this is treated as a deterministic config error and not
        retried (a real chip/host failure needs time to get going).
    """

    def __init__(self, cmd: Sequence[str],
                 elastic_config: Optional[Dict] = None,
                 world_size_fn=None, max_restarts: int = 3,
                 restart_delay_s: float = 5.0,
                 min_uptime_s: float = 0.0,
                 env: Optional[Dict[str, str]] = None):
        self.cmd = list(cmd)
        self.elastic_config = elastic_config
        self.world_size_fn = world_size_fn
        self.max_restarts = max_restarts
        self.restart_delay_s = restart_delay_s
        self.min_uptime_s = min_uptime_s
        self.env = env
        self.attempts: List[int] = []          # exit codes observed

    def _world_size(self) -> int:
        if self.world_size_fn is not None:
            return int(self.world_size_fn())
        import jax
        return jax.device_count()

    def _start_env(self, restart: int) -> Dict[str, str]:
        env = dict(os.environ)
        if self.env:
            env.update(self.env)
        env.update(_elastic_env_vars(self.elastic_config,
                                     self._world_size(), restart))
        return env

    def run(self) -> int:
        """Run to completion (0) or until restarts are exhausted (last
        non-zero exit code)."""
        from .elasticity import ElasticityIncompatibleWorldSize

        restart = 0
        last_rc = 1
        while True:
            try:
                env = self._start_env(restart)
            except ElasticityIncompatibleWorldSize as e:
                # the surviving world cannot run any compatible batch —
                # a restart would fail identically; surface it as a clean
                # give-up, not a supervisor crash
                logger.error(f"elastic agent: giving up — {e}")
                return last_rc
            if restart:
                logger.warning(
                    f"elastic agent: restart {restart}/{self.max_restarts} "
                    f"(previous exits: {self.attempts})")
            t0 = time.monotonic()
            proc = subprocess.run(self.cmd, env=env)
            uptime = time.monotonic() - t0
            self.attempts.append(proc.returncode)
            last_rc = proc.returncode
            if proc.returncode == 0:
                return 0
            if (restart == 0 and self.min_uptime_s > 0
                    and uptime < self.min_uptime_s):
                logger.error(
                    f"elastic agent: first attempt died after {uptime:.1f}s "
                    f"(< min_uptime_s={self.min_uptime_s}) — treating as a "
                    f"config error, not retrying")
                return proc.returncode
            if restart >= self.max_restarts:
                logger.error(
                    f"elastic agent: giving up after {restart} restarts "
                    f"(exit codes {self.attempts})")
                return proc.returncode
            restart += 1
            time.sleep(self.restart_delay_s)


class PodElasticAgent:
    """Pod-level elastic supervision (the local agent alone restarts
    one host's workers; a pod needs its membership re-formed): rank-0's host
    runs this agent; it fans the training command out over the pod's
    hosts (launcher.multinode_runner.SSHRunner) and, when a host dies,
    restarts the WHOLE fan-out over the surviving membership with the
    elastic batch recomputed for the smaller world.

    Reference: `deepspeed/elasticity/elastic_agent.py:32` DSElasticAgent
    — torch-elastic's rendezvous re-admits workers and restarts with the
    new WORLD_SIZE.  The TPU shape has no per-worker rendezvous: XLA's
    collectives need a consistent mesh from process start, so membership
    change == full job restart (megascale behaves the same way), and
    recovery is checkpoint-based exactly like the reference
    (`load_checkpoint(latest)` in the restarted script; universal
    checkpointing makes the world-size change safe).

    Division of labor with `DSElasticAgent`: that class supervises ONE
    process (single-host in-band restarts); this one supervises the
    fan-out and owns membership.  Failure attribution comes from the
    runner (`last_failed_hosts`) plus an optional `health_fn(host)`
    probe that decides whether a failed host may rejoin the next
    attempt (default: failed hosts stay out — a flapping host would
    otherwise burn every restart budget).

    Args:
      cmd: training argv, identical on every host.
      hosts: {host: chips} pod membership (hostfile format).
      elastic_config: dict with the "elasticity" section; each attempt
        exports DSTPU_ELASTIC_{BATCH,MICRO,WORLD} through the runner.
      health_fn: optional (host) -> bool liveness probe applied to
        FAILED hosts before each restart; returning True re-admits.
      runner_factory: (hosts: Dict[str, int], extra_env) -> runner with
        .launch(cmd) -> rc and .last_failed_hosts; defaults to
        SSHRunner.  Injectable for tests.
      max_restarts / restart_delay_s / min_uptime_s: as in
        DSElasticAgent (min_uptime_s guards against evicting healthy
        hosts on a deterministic config error: a FIRST attempt that dies
        faster than this gives up instead of shrinking the pod).
      min_hosts: give up (rather than restart) when the surviving
        membership drops below this.
    """

    def __init__(self, cmd: Sequence[str], hosts: Dict[str, int],
                 elastic_config: Optional[Dict] = None,
                 health_fn=None, runner_factory=None,
                 max_restarts: int = 3, restart_delay_s: float = 5.0,
                 min_uptime_s: float = 0.0, min_hosts: int = 1):
        self.cmd = list(cmd)
        self.hosts: Dict[str, int] = dict(hosts)
        self.elastic_config = elastic_config
        self.health_fn = health_fn
        self.runner_factory = runner_factory or self._default_runner
        self.max_restarts = max_restarts
        self.restart_delay_s = restart_delay_s
        self.min_uptime_s = min_uptime_s
        self.min_hosts = min_hosts
        self.attempts: List[Dict] = []   # per-attempt {hosts, rc, failed}

    @staticmethod
    def _default_runner(hosts: Dict[str, int], extra_env: Dict[str, str]):
        from ..launcher.multinode_runner import SSHRunner
        return SSHRunner(hosts, extra_env=extra_env)

    def _elastic_env(self, live: Dict[str, int], restart: int
                     ) -> Dict[str, str]:
        world = sum(live.values())
        slots = set(live.values())
        # uniform pods feed the v0.2 host-granular math its chip count;
        # heterogeneous slots fall back to v0.1 chip-granular worlds
        chips = slots.pop() if len(slots) == 1 else 1
        return _elastic_env_vars(self.elastic_config, world, restart,
                                 chips_per_host=chips)

    def run(self) -> int:
        from .elasticity import ElasticityIncompatibleWorldSize

        live = dict(self.hosts)
        restart = 0
        last_rc = 1
        while True:
            if len(live) < self.min_hosts:
                logger.error(
                    f"pod elastic agent: {len(live)} hosts left "
                    f"(< min_hosts={self.min_hosts}) — giving up")
                return last_rc
            try:
                env = self._elastic_env(live, restart)
            except ElasticityIncompatibleWorldSize as e:
                logger.error(f"pod elastic agent: giving up — {e}")
                return last_rc
            if restart:
                logger.warning(
                    f"pod elastic agent: restart {restart}/"
                    f"{self.max_restarts} over {sorted(live)} "
                    f"(world={env['DSTPU_ELASTIC_WORLD']})")
            runner = self.runner_factory(dict(live), env)
            t0 = time.monotonic()
            rc = runner.launch(self.cmd)
            uptime = time.monotonic() - t0
            failed = list(getattr(runner, "last_failed_hosts", []))
            self.attempts.append(
                {"hosts": sorted(live), "rc": rc, "failed": failed})
            last_rc = rc
            if rc == 0:
                return 0
            if (restart == 0 and self.min_uptime_s > 0
                    and uptime < self.min_uptime_s):
                logger.error(
                    f"pod elastic agent: first attempt died after "
                    f"{uptime:.1f}s (< min_uptime_s={self.min_uptime_s}) "
                    f"— treating as a config error, not evicting hosts "
                    f"or retrying")
                return rc
            # membership update: failed hosts leave unless the health
            # probe clears them for re-admission
            for h in failed:
                if self.health_fn is not None and self.health_fn(h):
                    logger.warning(
                        f"pod elastic agent: host {h} failed but probes "
                        f"healthy — keeping it in the membership")
                    continue
                live.pop(h, None)
            if restart >= self.max_restarts:
                logger.error(
                    f"pod elastic agent: giving up after {restart} "
                    f"restarts (attempts: {self.attempts})")
                return rc
            restart += 1
            time.sleep(self.restart_delay_s)
