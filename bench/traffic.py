"""One general request generator, driven by a traffic file and a seed.

A traffic file fixes the shape of the load: how many requests, their
arrival process and rate (virtual seconds, the simulator's clock), the
tenant mix, the prompt and answer lengths and the devices that send the
requests.  The shape is drawn once from the file's own ``shape_seed``, so
every run seed gets the same multiset of gaps, tenants, lengths and
devices; the run seed only permutes them within blocks of
``permute_block`` requests and draws the prompt tokens.  Runs with
different seeds then do the same work in another order, and their numbers
differ by the system's noise, not by the draw.  Where the order itself is
what a metric reads (a queue's tail below the knee is set by its bursts,
and a burst is an order), ``permute_block`` 1 keeps the shape's order for
every seed, which then draws only the prompt tokens.

Length fields take an integer or ``{"lognormal": {"median": m, "sigma": s,
"min": a, "max": b}}``.  Arrival processes are ``poisson`` (``rate_hz``)
and ``onoff`` (``rate_hz`` during on periods of mean ``on_s``, silence for
mean ``off_s``).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

# stream tags keep the seed's draws independent of each other
_PROMPTS, _ORDER = 1, 3


def _lengths(spec, n: int, rng: np.random.Generator) -> np.ndarray:
    if isinstance(spec, int):
        return np.full(n, spec, np.int64)
    ln = spec["lognormal"]
    x = rng.lognormal(np.log(ln["median"]), ln["sigma"], n)
    return np.clip(np.rint(x), ln["min"], ln["max"]).astype(np.int64)


def _gaps(arr: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps in virtual seconds."""
    rate = float(arr["rate_hz"])
    if arr["process"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    if arr["process"] == "onoff":
        gaps = rng.exponential(1.0 / rate, n)
        # each gap that ends an on period carries an off period with it
        ends_on = rng.random(n) < (1.0 / rate) / float(arr["on_s"])
        return gaps + ends_on * rng.exponential(float(arr["off_s"]), n)
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def shape(traffic: Dict) -> Dict[str, np.ndarray]:
    """The seed-independent multiset of the load: gap, tenant index,
    prompt length, answer length and sending device of every request, in
    draw order."""
    n = int(traffic["requests"])
    rng = np.random.default_rng(int(traffic["shape_seed"]))
    tenants = traffic["tenants"]
    w = np.asarray([t["weight"] for t in tenants], float)
    tenant = rng.choice(len(tenants), size=n, p=w / w.sum())
    new = np.empty(n, np.int64)
    for i, t in enumerate(tenants):
        sel = tenant == i
        new[sel] = _lengths(t["max_new_tokens"], int(sel.sum()), rng)
    return {"gap": _gaps(traffic["arrivals"], n, rng),
            "tenant": tenant,
            "prompt_len": _lengths(traffic["prompt_len"], n, rng),
            "max_new_tokens": new,
            "device": rng.integers(0, int(traffic["topology"]["devices"]),
                                   n)}


def _block_permutation(n: int, block: int,
                       rng: np.random.Generator) -> np.ndarray:
    order = np.arange(n)
    for a in range(0, n, block):
        order[a:a + block] = a + rng.permutation(min(block, n - a))
    return order


def make_requests(traffic: Dict, *, vocab_size: int, seed: int,
                  factory: Callable, count: int = None) -> List:
    """The request list of one run: ``factory(**fields)`` is called once
    per request with the fields of ``repro.fleet.workload.FleetRequest``.
    ``count`` takes the first requests only (the warm-up's share); with
    ``vocab_size`` 0 the requests carry no prompt tokens (timing only)."""
    sh = shape(traffic)
    n = len(sh["gap"])
    rng = lambda tag: np.random.default_rng([tag, int(seed)])  # noqa: E731
    order = _block_permutation(n, int(traffic.get("permute_block", 32)),
                               rng(_ORDER))
    sh = {k: v[order] for k, v in sh.items()}
    arrival = np.cumsum(sh["gap"])
    prng = rng(_PROMPTS)
    tenants: Sequence[Dict] = traffic["tenants"]
    out = []
    for i in range(n if count is None else min(count, n)):
        t = tenants[int(sh["tenant"][i])]
        plen = int(sh["prompt_len"][i])
        out.append(factory(
            rid=i, device=int(sh["device"][i]), tenant=t["name"],
            slo_s=float(t["slo_s"]),
            max_new_tokens=int(sh["max_new_tokens"][i]),
            arrival_s=float(arrival[i]), prompt_len=plen,
            prompt=prng.integers(0, vocab_size, plen).astype(np.int32)
            if vocab_size else None))
    return out
