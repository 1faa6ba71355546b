"""WorldCommunicator: async, fault-tolerant collective operations (paper §3.3).

Supports the paper's 8 collective operations — ``send``, ``recv``,
``broadcast``, ``all_reduce``, ``reduce``, ``all_gather``, ``gather``,
``scatter`` — each taking the world name as an argument (the paper's
backward-compatible API: "including a world name as a function argument
suffices").

Non-blocking execution model: every op is a coroutine driven by busy-wait
polling with an explicit scheduler yield per poll (``await asyncio.sleep(0)``)
— the paper's "we mitigate the throughput loss of polling via busy waiting,
but at the same time we make sure that other tasks can be scheduled
immediately if the operation is pending". This is what prevents the rhombus
deadlock of Fig. 2: a pending ``recv`` from P2 never blocks a ``recv`` from P3.

Fault semantics: every poll iteration re-checks the world's status. When the
watchdog/WorldManager fences a world, all pending ops on it abort with
:class:`WorldBrokenError` on their next poll; a detectable remote crash
(``RemoteError``, the ncclRemoteError analogue) is caught, reported to the
manager (which fences the world), and surfaced as ``WorldBrokenError`` too.

Ordering contract (same as NCCL): all ranks of a world must issue collectives
in the same order; point-to-point ops between a (src, dst) pair are FIFO.
"""
from __future__ import annotations

import asyncio
import time
from typing import Any, Callable, Sequence

import jax.numpy as jnp

from .fault import RemoteError, WorldBrokenError, WorldNotFoundError
from .world import World, WorldStatus

ReduceFn = Callable[[Any, Any], Any]

REDUCE_OPS: dict[str, ReduceFn] = {
    "sum": lambda a, b: a + b,
    "prod": lambda a, b: a * b,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


class WorldCommunicator:
    def __init__(self, manager) -> None:
        self._manager = manager
        self.worker_id = manager.worker_id
        #: world -> number of in-flight ops (introspection; the manager's
        #: abort path is status-based, so no future plumbing is needed)
        self.pending: dict[str, int] = {}
        self.ops_completed = 0
        self.ops_aborted = 0
        #: pending poll iterations that found nothing (the busy-wait's cost
        #: on the shared event loop)
        self.polls_empty = 0
        self._ops_since_yield = 0
        self._rank_cache: dict[str, tuple[World, int]] = {}

    #: fairness: an op that completes without ever pending still yields to
    #: the scheduler every N ops, so a tight send/recv loop cannot starve
    #: watchdog heartbeats and timers on the shared event loop
    FAIRNESS_EVERY = 64

    # ------------------------------------------------------------------ utils
    def _world(self, name: str) -> tuple[World, int]:
        """Resolve (world, my rank); hot path — memoized per world object.

        The cache is keyed on the World instance so re-initialized worlds
        (new object under the same name) re-resolve, and status is *always*
        re-checked by the caller's poll loop, never cached.
        """
        world = self._manager.worlds.get(name)
        if world is None or world.status is WorldStatus.REMOVED:
            self._rank_cache.pop(name, None)
            # removed worlds never see another op: drop their pending counter
            # too, or every scale/heal cycle leaks one dict entry per world
            self.pending.pop(name, None)
            raise WorldNotFoundError(name)
        cached = self._rank_cache.get(name)
        if cached is not None and cached[0] is world:
            return world, cached[1]
        rank = world.rank_of(self.worker_id)
        if rank is None:
            raise WorldNotFoundError(f"{name} (worker {self.worker_id} not a member)")
        self._rank_cache[name] = (world, rank)
        return world, rank

    def _check_broken(self, world: World) -> None:
        if world.status is WorldStatus.BROKEN:
            raise WorldBrokenError(world.name, world.broken_reason)
        if world.status is WorldStatus.REMOVED:
            raise WorldNotFoundError(world.name)

    def _attempt(self, world: World, fn: Callable[[], tuple[bool, Any]]
                 ) -> tuple[bool, Any]:
        try:
            return fn()
        except RemoteError as e:
            # ncclRemoteError path: report, fence, abort (paper §3.2)
            self._manager.report_broken(world.name, str(e))
            raise WorldBrokenError(world.name, str(e)) from e

    async def _finish(self, value: Any) -> Any:
        self.ops_completed += 1
        self._ops_since_yield += 1
        if self._ops_since_yield >= self.FAIRNESS_EVERY:
            self._ops_since_yield = 0
            await asyncio.sleep(0)
        return value

    async def _poll(self, world: World, fn: Callable[[], tuple[bool, Any]],
                    timeout: float | None) -> Any:
        """Busy-wait poll ``fn`` until it reports done, aborting if the world
        breaks. One scheduler yield per pending iteration."""
        try:
            # fast path: most ops complete on the first attempt — skip all
            # pending bookkeeping and deadline setup
            self._check_broken(world)
            done, value = self._attempt(world, fn)
            if done:
                return await self._finish(value)

            self.pending[world.name] = self.pending.get(world.name, 0) + 1
            deadline = None if timeout is None else time.monotonic() + timeout
            try:
                while True:
                    await asyncio.sleep(0)
                    self._check_broken(world)
                    done, value = self._attempt(world, fn)
                    if done:
                        return await self._finish(value)
                    self.polls_empty += 1
                    if deadline is not None and time.monotonic() > deadline:
                        raise TimeoutError(
                            f"op on world '{world.name}' timed out after "
                            f"{timeout}s")
            finally:
                # prune on zero: ``pending`` holds only worlds with in-flight
                # ops, instead of growing one permanent key per world ever
                # used across every scale/heal cycle
                n = self.pending.get(world.name, 1) - 1
                if n <= 0:
                    self.pending.pop(world.name, None)
                else:
                    self.pending[world.name] = n
        except WorldBrokenError:
            self.ops_aborted += 1
            raise

    # ----------------------------------------------------------- point-to-point
    async def send(self, tensor: Any, dst: int, world_name: str,
                   timeout: float | None = None) -> None:
        world, rank = self._world(world_name)

        def _try() -> tuple[bool, Any]:
            self._manager.transport.send(
                world_name, rank, dst, tensor,
                dst_worker=world.members.get(dst),
                src_worker=world.members.get(rank))
            return True, None

        await self._poll(world, _try, timeout)

    async def recv(self, src: int, world_name: str,
                   timeout: float | None = None) -> Any:
        world, rank = self._world(world_name)

        def _try() -> tuple[bool, Any]:
            return self._manager.transport.recv_nowait(
                world_name, src, rank, src_worker=world.members.get(src))

        return await self._poll(world, _try, timeout)

    # --------------------------------------------------------------- collectives
    async def broadcast(self, tensor: Any, root: int, world_name: str,
                        timeout: float | None = None) -> Any:
        world, rank = self._world(world_name)
        if rank == root:
            for r in range(world.size):
                if r != root:
                    await self.send(tensor, r, world_name, timeout)
            return tensor
        return await self.recv(root, world_name, timeout)

    async def reduce(self, tensor: Any, root: int, world_name: str,
                     op: str = "sum", timeout: float | None = None) -> Any:
        world, rank = self._world(world_name)
        fn = REDUCE_OPS[op]
        if rank == root:
            acc = tensor
            for r in range(world.size):
                if r != root:
                    acc = fn(acc, await self.recv(r, world_name, timeout))
            return acc
        await self.send(tensor, root, world_name, timeout)
        return tensor

    async def all_reduce(self, tensor: Any, world_name: str, op: str = "sum",
                         timeout: float | None = None) -> Any:
        world, rank = self._world(world_name)
        reduced = await self.reduce(tensor, 0, world_name, op, timeout)
        return await self.broadcast(reduced if rank == 0 else None, 0,
                                    world_name, timeout)

    async def gather(self, tensor: Any, root: int, world_name: str,
                     timeout: float | None = None) -> list[Any] | None:
        world, rank = self._world(world_name)
        if rank == root:
            out: list[Any] = [None] * world.size
            out[root] = tensor
            for r in range(world.size):
                if r != root:
                    out[r] = await self.recv(r, world_name, timeout)
            return out
        await self.send(tensor, root, world_name, timeout)
        return None

    async def all_gather(self, tensor: Any, world_name: str,
                         timeout: float | None = None) -> list[Any]:
        world, rank = self._world(world_name)
        gathered = await self.gather(tensor, 0, world_name, timeout)
        return await self.broadcast(gathered if rank == 0 else None, 0,
                                    world_name, timeout)

    async def scatter(self, tensors: Sequence[Any] | None, root: int,
                      world_name: str, timeout: float | None = None) -> Any:
        world, rank = self._world(world_name)
        if rank == root:
            assert tensors is not None and len(tensors) == world.size, (
                f"scatter at root needs {world.size} tensors")
            for r in range(world.size):
                if r != root:
                    await self.send(tensors[r], r, world_name, timeout)
            return tensors[root]
        return await self.recv(root, world_name, timeout)
