"""Closed-loop SimPolicy consultations: one caller, back to back.

Each context is one loop of one application at one time step on one
machine; ``SimPolicy(LoopWhatIf(machine), reward).decide()`` prices every
candidate of the portfolio on the device and returns a ``Decision``.  One
``LoopWhatIf`` and one policy are held per machine, as a runtime holds them.

Set-up consults step 0 of every (loop, machine); the window visits the
(loop, machine) pairs in turn, each at its steps 1..T-1 in an order drawn
from the seed, so every window holds the same mix of pairs, and times each
``decide`` from call to returned decision.

Output check: after the window, a sample of decisions drawn from the seed
has every candidate price the program computed compared with the plain
reference's price of the same candidate, and the decision ``decide``
returned compared with the reference's decision on the reference's prices:
the cheapest candidate, or STATIC when all prices lie within the
configuration's confidence threshold (the policy's fallback ladder starts at
STATIC, and a consultation reports nothing back).  The decision's number is
the relative gap between the reference prices of the two, 0 where they
agree.
"""

from __future__ import annotations

import time

import numpy as np

from reference import lb4omp as ref
from reference import selectors as sel


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.seed = ctx.seed
        self.latencies = []
        self.records = []       # (app, loop index, t, machine, priced)
        self.cache_hits = 0
        self.errors = 0
        self._priced = None

    def setup(self):
        from repro.core import SimPolicy
        from repro.sim import LoopWhatIf, get_application, get_system
        from repro.sim.backends.jax_batched import JaxBatchedBackend

        tr = self.traffic
        self.bk = bk = JaxBatchedBackend(data_parallel=self.ctx.chips)
        systems = (list(self.cfg["systems"]) if tr["systems"] == "all"
                   else tr["systems"])
        self.apps = {a: get_application(a) for a, _ in tr["loops"]}
        self.policies = {}
        for s in systems:
            wi = LoopWhatIf(get_system(s), backend=bk)
            pol = SimPolicy(wi, reward=tr["reward"])
            self.ctx.spans.wrap(pol, "decide", "SimPolicy.decide")
            self.policies[s] = (wi, pol)
        self.ctx.spans.wrap(bk, "run_batch", "run_batch", after=self._capture)
        self.contexts = [(a, li, t, s) for s in systems
                         for a, li in tr["loops"]
                         for t in range(self.cfg["apps"][a]["T"])]
        for a, li, t, s in self.contexts:
            if t == 0:
                self._decide(a, li, t, s)
        self.records.clear()
        self.latencies.clear()
        self.ctx.spans.on = False

    def _capture(self, args, kwargs, out):
        _, system, specs = args
        self._priced = ([(s.alg, s.chunk_param, s.seed) for s in specs],
                        np.array(out.loop_time))

    def _decide(self, a, li, t, s):
        wi, pol = self.policies[s]
        profile = self.apps[a].loops(t)[li]
        wi.set_context(profile, 0)
        self._priced = None
        t0 = time.perf_counter()
        try:
            d = pol.decide()
        except Exception:           # a failed consultation counts as failed
            self.errors += 1
            return
        self.latencies.append(time.perf_counter() - t0)
        if self._priced is None:
            self.cache_hits += 1
        self.records.append((a, li, t, s, self._priced,
                             (d.action, d.chunk_param)))

    def reset(self, seed: int) -> None:
        self.seed = seed
        self.records, self.latencies = [], []
        self.cache_hits = self.errors = 0

    def window(self, seconds: float) -> dict:
        pairs = sorted({(a, li, s) for a, li, _, s in self.contexts})
        steps = [np.random.default_rng((self.seed, k)).permutation(
            np.arange(1, self.cfg["apps"][a]["T"]))
            for k, (a, _, _) in enumerate(pairs)]
        order = [(a, li, int(ts[i]), s)
                 for i in range(min(map(len, steps)))
                 for (a, li, s), ts in zip(pairs, steps)]
        self.ctx.spans.on = True
        t0 = time.perf_counter()
        k = 0
        while time.perf_counter() - t0 < seconds:
            self._decide(*order[k % len(order)])
            k += 1
        wall = time.perf_counter() - t0
        self.ctx.spans.on = False
        lat = np.asarray(self.latencies)
        return {"decide_p95_ms": float(np.percentile(lat, 95) * 1e3),
                "_decide_p50_ms": float(np.median(lat) * 1e3),
                "_decisions": len(lat), "_window_s": wall,
                "_cache_hits": self.cache_hits}

    def release(self):
        self.policies = None

    def check(self, control: bool = False) -> dict:
        chk = self.traffic["check"]
        rng = np.random.default_rng((self.seed, 0xC0DE))
        priced = [i for i, r in enumerate(self.records) if r[4] is not None]
        attempted = len(self.records) + self.errors
        failed = self.errors + sum(
            int((~np.isfinite(r[4][1]) | (r[4][1] <= 0)).sum())
            for r in self.records if r[4] is not None)
        if not priced:
            return {"attempted": attempted, "failed": failed, "checks": {},
                    "sampled": 0}
        pick = rng.choice(priced, min(chk["sample"], len(priced)),
                          replace=False)
        thr = self.cfg["selectors"]["simpolicy"]["confidence_threshold"]
        gaps, ctl, choice = [], [], []
        for i in sorted(pick):
            a, li, t, s, (cands, prices), (action, cp_chosen) = \
                self.records[i]
            lp = ref.loop(self.cfg, a, li, t)
            m = ref.machine(self.cfg, s).noise_free()
            wants = []
            for (alg, cp, seed), price in zip(cands, prices):
                want = ref.instance(lp, m, alg, cp, seed)
                wants.append(want)
                gaps.append(ref.rel_gap(float(price), want))
                if control and not (alg == 0 or ref.needs_closed_form(
                        alg, lp.N, cp)):
                    ctl.append(ref.rel_gap(
                        ref.instance(lp, m, alg, cp, seed, ref.BF16), want))
            keys = [(alg, cp) for alg, cp, _ in cands]
            best = sel.sim_choice(wants, thr, keys.index((0, 0)))
            got = (action, 0 if cp_chosen is None else cp_chosen)
            choice.append(ref.rel_gap(wants[keys.index(got)], wants[best])
                          if got in keys else float("inf"))
        out = {"attempted": attempted, "failed": failed,
               "sampled": len(gaps),
               "checks": {"max_rel_gap": max(gaps),
                          "decision_gap": max(choice)}}
        if control:
            out["control"] = {"max_rel_gap": max(ctl, default=float("nan"))}
        return out
