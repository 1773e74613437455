"""Fault tolerance for 1000+-node operation, exercised here by simulation.

Three mechanisms (each unit-tested with injected failures):

* ``HeartbeatMonitor`` -- per-host step heartbeats; hosts whose last beat is
  older than ``timeout`` are dead, hosts slower than ``straggler_factor`` x
  median step time are stragglers.  At scale the scheduler uses this to
  evict/replace nodes before they stall the collective.  The same monitor
  doubles as stage-thread liveness for the stereo serving engine
  (:mod:`repro.serving.stereo_service`): each stage loop beats once per
  queue poll, so a wedged stage shows up as dead in
  ``StereoService.stats()``.
* ``run_with_recovery`` -- wraps the train loop: on failure, restores the
  latest checkpoint and replays.  Batches are a pure function of step
  (repro.data.tokens), so recovery is bitwise-deterministic.
* ``elastic_reshard`` -- re-lays-out a checkpoint onto a different mesh
  (fewer/more healthy hosts) via per-leaf device_put with the target
  NamedSharding; sharding rules are mesh-shape-agnostic so the same logical
  specs resolve on the new mesh.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding

from repro.distributed.sharding import ShardingRules, logical_to_spec


# --------------------------------------------------------------------------
# heartbeat / straggler detection
# --------------------------------------------------------------------------
@dataclasses.dataclass
class HostStatus:
    last_beat: float
    last_step: int
    step_times: list


class HeartbeatMonitor:
    def __init__(
        self,
        hosts: list[str],
        timeout: float = 60.0,
        straggler_factor: float = 2.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.timeout = timeout
        self.straggler_factor = straggler_factor
        self.clock = clock
        self.hosts = {
            h: HostStatus(last_beat=clock(), last_step=-1, step_times=[])
            for h in hosts
        }

    def beat(self, host: str, step: int) -> None:
        st = self.hosts.get(host)
        if st is None:      # late registration (e.g. a restarted stage thread)
            st = self.hosts[host] = HostStatus(
                last_beat=self.clock(), last_step=-1, step_times=[]
            )
        now = self.clock()
        if st.last_step >= 0 and step > st.last_step:
            st.step_times.append((now - st.last_beat) / (step - st.last_step))
            st.step_times = st.step_times[-20:]
        st.last_beat = now
        st.last_step = step

    def dead_hosts(self) -> list[str]:
        now = self.clock()
        return [
            h for h, st in self.hosts.items() if now - st.last_beat > self.timeout
        ]

    def stragglers(self) -> list[str]:
        times = {
            h: sum(st.step_times) / len(st.step_times)
            for h, st in self.hosts.items()
            if st.step_times
        }
        if len(times) < 2:
            return []
        ordered = sorted(times.values())
        median = ordered[len(ordered) // 2]
        return [
            h for h, t in times.items() if t > self.straggler_factor * median
        ]

    def is_alive(self, host: str) -> bool:
        """Whether ``host``'s last beat is within ``timeout`` (unknown
        hosts report dead -- they have never beaten)."""
        st = self.hosts.get(host)
        return st is not None and self.clock() - st.last_beat <= self.timeout

    def healthy_hosts(self) -> list[str]:
        bad = set(self.dead_hosts())
        return [h for h in self.hosts if h not in bad]


# --------------------------------------------------------------------------
# checkpoint-replay recovery
# --------------------------------------------------------------------------
def run_with_recovery(
    step_fn: Callable[[int, Any], Any],
    state: Any,
    start_step: int,
    num_steps: int,
    checkpoint_mgr,
    save_every: int,
    restore_fn: Callable[[], tuple[int, Any]],
    max_failures: int = 10,
) -> tuple[Any, int, int]:
    """Drive step_fn with checkpointing; on exception restore and replay.

    Returns (final_state, final_step, failures_recovered).
    """
    failures = 0
    step = start_step
    while step < start_step + num_steps:
        try:
            state = step_fn(step, state)
            step += 1
            if step % save_every == 0:
                checkpoint_mgr.save(step, state)
        except Exception:
            failures += 1
            if failures > max_failures:
                raise
            checkpoint_mgr.wait()
            step, state = restore_fn()
    checkpoint_mgr.wait()
    return state, step, failures


# --------------------------------------------------------------------------
# elastic re-scale
# --------------------------------------------------------------------------
def elastic_reshard(
    tree: Any,
    spec_tree: Any,
    new_mesh: Mesh,
    rules: ShardingRules,
) -> Any:
    """Re-lay-out a (host or device) pytree onto ``new_mesh``.

    spec_tree holds logical-axis tuples (the model's param_specs); they are
    re-resolved against the NEW mesh, so e.g. fsdp=("pod","data") simply
    drops the pod axis when the new mesh has none.
    """
    def put(leaf, axes):
        spec = logical_to_spec(axes, rules, new_mesh)
        return jax.device_put(leaf, NamedSharding(new_mesh, spec))

    # tree is the primary structure; spec entries at leaf positions are the
    # logical-axis tuples (flattened up to tree's structure).
    return jax.tree.map(put, tree, spec_tree)
