"""The one general traffic generator: a traffic file and a seed in, the
load generator's plan out.

A traffic file (`traffic/<name>.json`) holds parameters only:

  loop        "closed" (a client sends when its last answer is drained)
              or "open" (requests go out at `rate_per_s`, Poisson)
  clients     closed: concurrent clients; open: sender threads
  queue       "per_client": each client works through a list of its own
              (independent streams); "shared": the clients draw in turn
              from one list (a pool of connections behind one dashboard
              server), so the requests that enter the system, and their
              order, do not depend on which client is quicker
  statement   "plain" SQL text, or "prepared" (PREPARE once, EXECUTE USING)
  session     session properties every request of the window carries
  order       "sequence": each client repeats `shapes` in the order given,
              and a cycle once begun is finished; "weighted": shapes by
              `weight`, in blocks of `block` requests
  shapes      [{"shape", "weight", "per_run": [parameter names]}] — a
              parameter in `per_run` is drawn once per run, the others
              per request, all from the shape's own DOMAIN
  law         {"kind": "uniform"} or {"kind": "zipf", "s": 1.0} over the
              per-request parameter combinations, rank -> value permuted
              by the seed
  prefill_ranks   set-up sends the hottest N combinations of each shape
              once under the window's own session (0: none)
  requests_per_client   how many requests to draw for each client: more
              than a window can hold (running out fails the run loudly)
  throughput_over   "last_completion" or "window" (see run.py)
  verify_max_distinct, trace_slice_s   see run.py

Every seed gives the same amount and kind of work in another order. The
RANKS are a fixed design, the same for every seed: within each block the
count of each shape is fixed by the weights, and a shape's k ranks lie one
in each k-th of the law's mass, at an offset that steps through the
blocks by the golden ratio. The seed decides what the design is made of:
which value stands behind each rank, the values drawn once per run, the
order inside each block, and which client sends which stream of blocks
(with `queue: shared` there is one stream).
So no seed draws a window of mostly cold or mostly hot requests, and two
seeds differ as two dashboards with the same users do.
"""

from __future__ import annotations

import bisect
import itertools
import json
import math
import os
import random

from reference import HERE, load_by_path

GOLDEN = (5 ** 0.5 - 1) / 2


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _combinations(module, per_run: list) -> list:
    names = [n for n in module.DOMAIN if n not in per_run]
    return [dict(zip(names, values)) for values in
            itertools.product(*(module.DOMAIN[n] for n in names))]


def _cdf(law: dict, n: int) -> list:
    if law["kind"] == "uniform":
        weights = [1.0] * n
    elif law["kind"] == "zipf":
        weights = [1.0 / (r + 1) ** law["s"] for r in range(n)]
    else:
        raise ValueError(f"unknown law {law}")
    total, acc, out = sum(weights), 0.0, []
    for w in weights:
        acc += w
        out.append(acc / total)
    return out


def _rank(cdf: list, u: float) -> int:
    return min(bisect.bisect_left(cdf, u), len(cdf) - 1)


def _apportion(weights: list, n: int) -> list:
    """n requests over the shapes by weight, largest remainders first."""
    total = sum(weights)
    exact = [n * w / total for w in weights]
    counts = [math.floor(x) for x in exact]
    for i in sorted(range(len(weights)), key=lambda i: counts[i] - exact[i]
                    )[:n - sum(counts)]:
        counts[i] += 1
    return counts


def make_plan(traffic: dict, seed: int, seconds: float) -> dict:
    """Everything of the load generator's plan that the traffic decides."""
    entries = traffic["shapes"]
    modules = {e["shape"]: load_by_path("queries", e["shape"])
               for e in entries}
    per_run, values, cdfs = {}, {}, {}
    for e in entries:
        name, module = e["shape"], modules[e["shape"]]
        rng = random.Random(f"{seed}:per_run:{name}")
        per_run[name] = {p: rng.choice(module.DOMAIN[p])
                         for p in e.get("per_run", [])}
        combos = _combinations(module, e.get("per_run", []))
        random.Random(f"{seed}:values:{name}").shuffle(combos)
        values[name] = [{**per_run[name], **c} for c in combos]
        cdfs[name] = _cdf(traffic["law"], len(combos))

    def draw(name: str, block: int, k: int) -> list:
        """k ranks of one shape, one from each k-th of the law's mass."""
        offset = (block + 1) * GOLDEN % 1.0
        return [values[name][_rank(cdfs[name], (j + offset) / k)]
                for j in range(k)]

    n_clients = traffic["clients"]
    n_streams = 1 if traffic["queue"] == "shared" else n_clients
    streams = list(range(n_streams))
    random.Random(f"{seed}:streams").shuffle(streams)
    clients = []
    for c in range(n_streams):
        rng = random.Random(f"{seed}:client:{c}")
        reqs = []
        for b in itertools.count():
            if len(reqs) >= traffic["requests_per_client"] \
                    * n_clients // n_streams:
                break
            at = b * n_streams + streams[c]
            if traffic["order"] == "sequence":
                block = [(e["shape"], draw(e["shape"], at, 1)[0])
                         for e in entries]
            else:
                counts = _apportion([e["weight"] for e in entries],
                                    traffic["block"])
                block = [(e["shape"], params)
                         for e, k in zip(entries, counts) if k
                         for params in draw(e["shape"], at, k)]
                rng.shuffle(block)
            reqs.extend(block)
        clients.append(reqs)

    if traffic["loop"] == "open":
        # one Poisson schedule over the whole window, dealt to the clients
        rng = random.Random(f"{seed}:arrivals")
        t, i = 0.0, 0
        flat = [r for reqs in zip(*clients) for r in reqs]
        clients = [[] for _ in clients]
        while t < seconds and i < len(flat):
            t += rng.expovariate(traffic["rate_per_s"])
            clients[i % len(clients)].append((*flat[i], t))
            i += 1

    # set-up: one statement per shape with the result cache off, on
    # parameters of the next seed (the run's own per-run values); then
    # the hottest ranks under the window's own session
    setup = []
    for e in entries:
        rng = random.Random(f"{seed + 1}:warmup:{e['shape']}")
        setup.append({"phase": "warmup", "shape": e["shape"],
                      "params": rng.choice(values[e["shape"]]),
                      "session": {**traffic["session"],
                                  "result_cache_enabled": "false"}})
    for e in entries:
        for params in values[e["shape"]][:traffic["prefill_ranks"]]:
            setup.append({"phase": "prefill", "shape": e["shape"],
                          "params": params})

    return {
        "loop": traffic["loop"], "statement": traffic["statement"],
        "session": traffic["session"], "seconds": seconds,
        "queue": traffic["queue"], "n_clients": n_clients,
        "cycle": len(entries) if traffic["order"] == "sequence" else 1,
        "shapes": {name: {"sql": m.SQL, "prepared": m.PREPARED,
                          "using": m.USING}
                   for name, m in modules.items()},
        "per_run": per_run, "setup": setup, "clients": clients,
    }
