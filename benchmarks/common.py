"""Shared setup for the paper-reproduction benchmarks.

Builds the branchy AlexNet, trains it briefly on the synthetic CIFAR-like set
(so per-exit accuracies are *measured*, not assumed), profiles layers, and
arms the Edgent planner.  Cached across benchmark functions.
"""
from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EdgentPlanner, alexnet_graph
from repro.data.synthetic import cifar_like
from repro.launch.compile_cache import enable_compile_cache
from repro.models.alexnet import BranchyAlexNet, BranchyAlexNetConfig
from repro.optim.adamw import adamw_init, adamw_update

TRAIN_STEPS = int(os.environ.get("BENCH_TRAIN_STEPS", "120"))
BENCH_NOISE = float(os.environ.get("BENCH_NOISE", "1.2"))
KBPS = 125.0  # bytes/s per kbps


@functools.lru_cache(maxsize=1)
def alexnet_setup():
    enable_compile_cache()             # before the first compile below
    net = BranchyAlexNet(BranchyAlexNetConfig())
    rng = jax.random.key(0)
    params = net.init(rng)

    # --- quick BranchyNet joint training on synthetic CIFAR
    opt = adamw_init(params)
    step = jax.jit(lambda p, o, x, y, r: _train_step(net, p, o, x, y, r))
    data_rng = np.random.default_rng(0)
    r = rng
    for i in range(TRAIN_STEPS):
        x, y = cifar_like(data_rng, 64, noise=BENCH_NOISE)
        r, sub = jax.random.split(r)
        params, opt, loss = step(params, opt, jnp.asarray(x), jnp.asarray(y), sub)

    # --- measured per-exit accuracy on held-out data
    xv, yv = cifar_like(np.random.default_rng(123), 512, noise=BENCH_NOISE)
    acc = [float(net.accuracy(params, jnp.asarray(xv), jnp.asarray(yv), i))
           for i in range(1, net.num_exits + 1)]

    graph = alexnet_graph(net, accuracy=acc)
    x1 = jnp.asarray(xv[:1])
    planner = EdgentPlanner(graph, latency_req_s=1.0).offline_static(params, x1)
    return dict(net=net, params=params, graph=graph, planner=planner,
                accuracy=acc, sample=x1)


def _train_step(net, params, opt, x, y, rng):
    loss, grads = jax.value_and_grad(net.loss)(params, (x, y), rng)
    params, opt = adamw_update(grads, opt, params, lr=1e-3, weight_decay=1e-4)
    return params, opt, loss


def set_slo(planner: EdgentPlanner, slo_s: float):
    planner.latency_req_s = slo_s
    planner.static_opt.latency_req_s = slo_s


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.s = time.perf_counter() - self.t0

    @property
    def us(self) -> float:
        return self.s * 1e6
