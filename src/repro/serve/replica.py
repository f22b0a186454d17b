"""Replica abstraction for the multi-replica serving tier.

A *replica* is one independent `ServeEngine` behind a small uniform
surface the router (router.py) can drive without knowing where the
engine lives:

    submit(tokens, max_new, *, temperature, eos_id, uid, arrival_s)
    step() -> bool          # advance one engine iteration
    poll() -> [Completion]  # drain finished requests
    load() -> ReplicaLoad   # dispatch-cost inputs (queue/slots/pages)
    stats() -> EngineStats  # cumulative snapshot (gauges filled)
    pending -> bool
    close()

`InProcessReplica` wraps an engine in the router's own process, stepped
round-robin by the router; every replica shares the host's devices (and
the same `params` arrays — no copies). A replica lives in the router's
process because a chip belongs to one process at a time: a spawned
worker could not reach a chip its parent already holds.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol

from .engine import EngineStats, ServeEngine


@dataclasses.dataclass(frozen=True)
class ReplicaLoad:
    """Dispatch-cost inputs for one replica, read at routing time.

    `headroom` is the number of requests the replica could admit right
    now: free slots, further capped by free pages when the cache is
    paged (a worst-case request needs `pages_per_slot` pages)."""
    queue_depth: int            # requests waiting inside the engine
    free_slots: int
    slots: int
    pages_free: int = 0         # PagePool.available(); 0 for slot cache
    pages_per_slot: int = 0     # 0: not paged (pages don't bind)
    pending: bool = False
    planes: int = 1             # codebook count K: the engine's token
                                # counters count plane tokens, so
                                # utilization denominators scale by K

    @property
    def headroom(self) -> int:
        slots = self.free_slots
        if self.pages_per_slot > 0:
            slots = min(slots, self.pages_free // self.pages_per_slot)
        return slots


class Replica(Protocol):
    """Structural protocol — see module docstring for the contract."""

    def submit(self, prompt_tokens, max_new: int, *, temperature: float,
               eos_id, uid, arrival_s) -> int: ...
    def step(self) -> bool: ...
    def poll(self) -> list: ...
    def load(self) -> ReplicaLoad: ...
    def stats(self) -> EngineStats: ...
    @property
    def pending(self) -> bool: ...
    def close(self) -> None: ...


def _load_of(engine: ServeEngine) -> ReplicaLoad:
    return ReplicaLoad(
        queue_depth=len(engine.sched.queue),
        free_slots=len(engine.sched.free_slots()),
        slots=engine.ecfg.slots,
        pages_free=engine._pool.available() if engine.paged else 0,
        pages_per_slot=engine._n_per_slot if engine.paged else 0,
        pending=engine.sched.pending,
        planes=engine.K)


class InProcessReplica:
    """One ServeEngine in the router's process. step() runs one engine
    iteration (admission + one decode/prefill chunk round)."""

    def __init__(self, engine: ServeEngine):
        self.engine = engine

    def submit(self, prompt_tokens, max_new: int, *, temperature: float = 0.0,
               eos_id=None, uid=None, arrival_s=None) -> int:
        return self.engine.submit(prompt_tokens, max_new,
                                  temperature=temperature, eos_id=eos_id,
                                  uid=uid, arrival_s=arrival_s)

    def step(self) -> bool:
        return self.engine.step()

    def poll(self) -> list:
        done, self.engine.completions = self.engine.completions, []
        return done

    def load(self) -> ReplicaLoad:
        return _load_of(self.engine)

    def stats(self) -> EngineStats:
        return self.engine.snapshot()

    @property
    def pending(self) -> bool:
        return self.engine.sched.pending

    def close(self) -> None:
        pass
