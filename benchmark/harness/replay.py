"""Traffic: a mix's arrival schedule from its data file and the seed, and
the replay that sends sealed uploads by it and times each one.

A copy of ``loadgen/driver.py::ReplayDriver`` and ``schedule.py`` with what
they lacked for a measurement: every upload is timed from the instant it
was DUE (not from when a slot came free), the generator's own lateness is
recorded, and a retry stays inside the measured latency. One process, one
event loop, a fixed number of connections.
"""

from __future__ import annotations

import asyncio
import math
import time
from dataclasses import dataclass, field

import numpy as np


def n_uploads(traffic: dict, cfg: dict, seconds: float) -> int:
    """How many uploads the mix sends in a window of ``seconds``."""
    if traffic["arrival"] == "flood":
        return int(traffic.get("count") or cfg["updates_per_round"])
    if traffic["arrival"] == "poisson":
        # whole fold batches only: a remainder flush compiles a second
        # program inside the Update phase
        k = int(cfg["batch_size"])
        return max(k, int(traffic["rate_per_s"] * seconds) // k * k)
    raise ValueError(f"unknown arrival {traffic['arrival']!r}")


def schedule(traffic: dict, n: int, seed: int) -> list[tuple[float, int]]:
    """``(due offset in seconds, upload index)``, ascending. Every seed
    gives the same amount of work: a flood is all uploads at 0 in an order
    drawn from the seed; Poisson arrivals are the mix's own ``n`` exponential
    gaps (drawn from the mix's ``pattern_seed``, so every seed sends the same
    set of gaps) in an order drawn from the seed, scaled so that the last
    upload is due at ``(n - 1) / rate``."""
    rng = np.random.default_rng([int(seed), 0x74726166])
    order = rng.permutation(n)
    if traffic["arrival"] == "flood":
        return [(0.0, int(i)) for i in order]
    rate = float(traffic["rate_per_s"])
    # the mix's own gaps (the same multiset for every seed), in the seed's order
    gaps = np.random.default_rng(int(traffic.get("pattern_seed", 1))).exponential(1.0, n)
    gaps = gaps[rng.permutation(n)]
    gaps[0] = 0.0
    due = np.cumsum(gaps)
    if n > 1:
        due *= ((n - 1) / rate) / due[-1]
    return [(float(t), int(i)) for t, i in zip(due, order)]


@dataclass
class ReplayResult:
    t_open: float = 0.0  # monotonic instant of the first send
    t_last_ok: float = 0.0  # monotonic instant of the last 200
    sent: int = 0
    ok: list = field(default_factory=list)  # indices answered 200
    errors: int = 0
    shed: int = 0
    abandoned: int = 0
    unsent: int = 0  # still waiting when the window's cap came
    latency_s: dict = field(default_factory=dict)  # index -> due -> 200
    lateness_s: dict = field(default_factory=dict)  # index -> due -> first byte sent


async def replay(url: str, messages: dict, events: list, *, concurrency: int,
                 cap_seconds: float, timeout: float, max_shed_retries: int = 3) -> ReplayResult:
    """Send every upload at its due offset under a gate of ``concurrency``
    connections; stop starting new sends ``cap_seconds`` after the first."""
    from xaynet_tpu.sdk.client import ClientError, ClientShedError, HttpClient

    client = HttpClient(url, timeout=timeout, max_idle=concurrency)
    gate = asyncio.Semaphore(concurrency)
    res = ReplayResult()
    start = time.monotonic()
    res.t_open = start

    async def one(offset: float, index: int) -> None:
        delay = offset - (time.monotonic() - start)
        if delay > 0:
            await asyncio.sleep(delay)  # outside the gate: waiting holds no slot
        due = start + offset
        async with gate:
            if time.monotonic() - start > cap_seconds:
                res.unsent += 1
                return
            res.sent += 1
            res.lateness_s[index] = time.monotonic() - due
            for attempt in range(max_shed_retries + 1):
                try:
                    await client.send_message(messages[index])
                    now = time.monotonic()
                    res.ok.append(index)
                    res.latency_s[index] = now - due
                    res.t_last_ok = max(res.t_last_ok, now)
                    return
                except ClientShedError as err:
                    res.shed += 1
                    if attempt >= max_shed_retries:
                        res.abandoned += 1
                        return
                    await asyncio.sleep(min(2.0, err.retry_after or 0.1))
                except (ClientError, OSError, asyncio.TimeoutError):
                    res.errors += 1
                    return

    try:
        await asyncio.gather(*(one(offset, i) for offset, i in events))
    finally:
        client.close()
    return res


def percentile(values: list, q: float) -> float | None:
    """The ``q``-th percentile by the nearest-rank rule (no interpolation:
    the value of a request that really happened)."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
