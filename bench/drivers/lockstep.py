"""Lockstep selector replay (``ReplayBatch``) over a campaign grid.

Set-up builds the backend and warms every (machine, schedule length, row
count) program the replay can dispatch, then builds the lanes.  The window
steps the replay from t=0 (and again from t=0 if it reaches T).

Every run replays the same lanes from the traffic file's ``replay_seed``, so
every run does the same work; ``--seed`` sets the order of the lanes (and so
the rows of each device call) and the sample the check draws.  Lanes are
independent, so their results do not depend on that order.

Output check, after the window, against the plain reference:

* execution: a sample of lane-steps drawn from the seed (plus those holding
  the longest instances) is replayed from each lane's random-stream state at
  the start of the step: every instance's loop time and LIB as
  ``run_lockstep`` returned them against the reference's;
* selection: lanes drawn from the seed, the same number for every selector,
  are watched through the whole window.  At every step, the reference
  selector decides from the lane's state before the step (the reference's
  own initial state at t=0) and must pick the (algorithm, chunk parameter)
  the program ran; it then learns from the observation the program fed the
  lane, and must reach the lane's state after the step, bit for bit.  The
  observation itself is held to the reference by the execution check: the
  three-level reward compares it with earlier ones, so a rounding-level
  difference in a tie would flip a reward and is no fault of the learner.
"""

from __future__ import annotations

import time

import numpy as np

from reference import lb4omp as ref
from reference import selectors as sel

#: row buckets the event program pads a batch to: 8, 16, 32, ...
MIN_ROWS = 8
#: elements of one (rows, K) operand per device call
MAX_ELEMS = 1 << 22


def _pow2(n: int) -> int:
    b = MIN_ROWS
    while b < n:
        b *= 2
    return b


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.seed = ctx.seed
        self.records = []           # one per run_lockstep call in the window
        self.instances = 0
        self.steps = 0
        self.t = 0
        self.replay = 0
        self.watched = {}           # replay -> [(lane, key)] of watched lanes
        self.states = {}            # (replay, t, lane key) -> loop states
        self.capturing = False
        self._pre = {}
        self.kernel_work = []       # (event-core chunk counts, P) per call

    # -- set-up ------------------------------------------------------------
    def _lanes(self):
        from repro.sim import CellSpec

        tr = self.traffic
        apps = list(self.cfg["apps"]) if tr["apps"] == "all" else tr["apps"]
        systems = (list(self.cfg["systems"]) if tr["systems"] == "all"
                   else tr["systems"])
        lanes = [CellSpec(app=a, system=s, selector=sel, chunk_mode=mode,
                          reward=rw)
                 for a in apps for s in systems
                 for mode in tr["chunk_modes"]
                 for sel, rw in tr["selectors"]]
        return lanes, apps, systems

    def setup(self):
        from repro.sim import (InstanceSpec, get_application, get_system)
        from repro.sim.backends.jax_batched import JaxBatchedBackend
        from repro.sim.campaign import chunk_param_for

        self.bk = bk = JaxBatchedBackend(data_parallel=self.ctx.chips)
        self.lanes, apps, systems = self._lanes()
        modes = self.traffic["chunk_modes"]
        n_sel = len(self.traffic["selectors"])
        # every event program the replay can dispatch: per machine, each
        # schedule length bucket K at every row bucket up to the most
        # instances that can share K in one step
        for sysname in systems:
            system = get_system(sysname)
            profiles = [lp for a in apps for lp in get_application(a).loops(0)]
            combos = []
            for pid, lp in enumerate(profiles):
                for mode in modes:
                    cp = chunk_param_for(mode, lp.N, system.P)
                    for alg in range(1, len(self.cfg["algorithms"])):
                        if not ref.needs_closed_form(alg, lp.N, cp):
                            combos.append((pid, alg, cp, mode))
            specs = [InstanceSpec(pid, alg, cp, (0, i))
                     for i, (pid, alg, cp, _) in enumerate(combos)]
            res = bk.run_batch(profiles, system, specs)
            by_k = {}
            for (pid, alg, cp, mode), n in zip(combos, res.n_chunks):
                by_k.setdefault(ref.k_bucket(int(n)), {}).setdefault(
                    (pid, mode), (pid, alg, cp))
            for K, slots in by_k.items():
                most = len(slots) * n_sel
                cap = max(MIN_ROWS, MAX_ELEMS // K)
                pick = list(slots.values())
                rows = MIN_ROWS
                while True:
                    n = min(rows, cap)
                    warm = [InstanceSpec(*pick[i % len(pick)], (1, i))
                            for i in range(n)]
                    bk.run_batch(profiles, system, warm)
                    if rows >= min(_pow2(most), _pow2(cap)):
                        break
                    rows *= 2
        self._new_replay()
        self.ctx.spans.wrap(bk, "run_lockstep", "run_lockstep",
                            after=self._capture)
        self.ctx.spans.on = False

    def _new_replay(self):
        from repro.sim import ReplayBatch

        order = np.random.default_rng((self.seed, 0x1A7E)).permutation(
            len(self.lanes))
        self.rb = ReplayBatch([self.lanes[i] for i in order],
                              T=self.traffic["T"],
                              seed=self.traffic["replay_seed"],
                              backend=self.bk)
        self.t = 0
        self.replay += 1
        # watched lanes: the same number of every selector, drawn from the
        # seed
        pick = np.random.default_rng((self.seed, 0x5E1))
        kinds = {}
        for i, lane in enumerate(self.rb.lanes):
            kinds.setdefault((lane.spec.selector, lane.spec.reward),
                             []).append(i)
        n = self.traffic["check"]["watch_per_selector"]
        self.watched[self.replay] = [
            (self.rb.lanes[i], i) for k in sorted(kinds, key=str)
            for i in sorted(pick.choice(kinds[k], min(n, len(kinds[k])),
                                        replace=False))]
        self.ctx.spans.wrap(self.rb, "step", "ReplayBatch.step")

    def reset(self, seed: int) -> None:
        """A fresh replay from t=0 under another seed, shapes still warm."""
        self.seed = seed
        self.records, self.kernel_work = [], []
        self.watched, self.states = {}, {}
        self.instances = self.steps = 0
        self._new_replay()

    def _snapshot(self) -> None:
        """Every watched lane's selector states after the step just run."""
        for lane, key in self.watched[self.replay]:
            regions = lane.service._regions
            self.states[(self.replay, self.t, key)] = [
                _state(regions[nm].policy) if nm in regions else None
                for nm in lane.app.loop_names]

    # -- capture -----------------------------------------------------------
    def _capture(self, args, kwargs, out):
        if not self.capturing:
            return
        profiles, system, requests = args
        self.records.append((self.t, system.name, profiles, [
            (q.profile_id, q.alg, q.chunk_param, id(q.rng)) for q in requests],
            self._pre, np.array(out.loop_time), np.array(out.n_chunks),
            self.replay, np.array(out.lib)))
        self.instances += len(requests)
        event = [not (q.alg == 0 or ref.needs_closed_form(
            q.alg, profiles[q.profile_id].N, q.chunk_param)) for q in requests]
        self.kernel_work.append((np.asarray(out.n_chunks)[event], system.P))

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        self.capturing = True
        self.ctx.spans.on = True
        inner = self.bk.run_lockstep

        def with_states(profiles, system, requests):
            # each lane's stream position at the start of its step: what the
            # reference replays from (taken before the program draws)
            self._pre = {id(q.rng): q.rng.bit_generator.state
                         for q in requests}
            return inner(profiles, system, requests)

        self.bk.run_lockstep = with_states
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if self.t >= self.traffic["T"]:
                self._new_replay()
            self.rb.step(self.t)
            self.t += 1
            self._snapshot()
            self.steps += 1
        wall = time.perf_counter() - t0
        self.capturing = False
        self.ctx.spans.on = False
        self.bk.run_lockstep = inner
        return {"sim_instances_per_s": self.instances / wall,
                "_window_s": wall, "_steps": self.steps,
                "_instances": self.instances}

    def kernel_calls(self):
        return self.kernel_work

    # -- check -------------------------------------------------------------
    def release(self):
        self.rb = None

    def check(self, control: bool = False) -> dict:
        """The numbers compared: the largest relative gap between the
        program's loop times and the reference's, the largest gap of LIB in
        percentage points, and the watched lanes' selection steps whose
        choice or learned state differs from the reference selector's.  With
        ``control`` also the gaps of the reference computed in bfloat16 (the
        control) on the same instances."""
        chk = self.traffic["check"]
        rng = np.random.default_rng((self.seed, 0xC0DE))
        lane_steps = []          # (record index, lane rng id)
        longest = []
        for r, rec in enumerate(self.records):
            reqs, nch = rec[3], rec[6]
            ids = list(dict.fromkeys(q[3] for q in reqs))
            lane_steps += [(r, i) for i in ids]
            j = int(np.argmax(nch))
            longest.append((int(nch[j]), r, reqs[j][3]))
        failed = sum(int((~np.isfinite(rec[5]) | (rec[5] <= 0)).sum())
                     for rec in self.records)
        if not lane_steps:
            return {"attempted": 0, "failed": failed, "checks": {},
                    "sampled": 0}
        pick = set(map(tuple, (lane_steps[i] for i in rng.choice(
            len(lane_steps), min(chk["sample"], len(lane_steps)),
            replace=False))))
        pick |= {(r, lid) for _, r, lid in sorted(longest)[-chk["longest"]:]}
        apps_by_n = {a["N"]: (name, a) for name, a in self.cfg["apps"].items()}
        gaps, lib_gaps, ctl, ctl_lib = [], [], [], []
        compared = 0
        for r, lid in sorted(pick):
            t, sysname, profiles, reqs, states, lt, _, _, lib = self.records[r]
            m = ref.machine(self.cfg, sysname)
            lane_rng = np.random.default_rng()
            lane_rng.bit_generator.state = states[lid]
            for i, (pid, alg, cp, qid) in enumerate(reqs):
                if qid != lid:
                    continue
                prof = profiles[pid]
                app, a = apps_by_n[prof.N]
                li = [lp["name"] for lp in a["loops"]].index(prof.name)
                lp = ref.loop(self.cfg, app, li, t)
                if alg == 0 or ref.needs_closed_form(alg, lp.N, cp):
                    fin = ref.closed_finish(lp, m, alg, cp, lane_rng)
                    want, want_lib = float(fin.max()), ref.lib(fin)
                else:
                    s = ref.fold_seed((int(lane_rng.integers(0, 2**31 - 1)),))
                    want, _, want_lib = ref.event_instance(lp, m, alg, cp, s)
                    if control:
                        c_mk, _, c_lib = ref.event_instance(lp, m, alg, cp, s,
                                                            ref.BF16)
                        ctl.append(ref.rel_gap(c_mk, want))
                        ctl_lib.append(abs(c_lib - want_lib))
                gaps.append(ref.rel_gap(float(lt[i]), want))
                lib_gaps.append(abs(float(lib[i]) - want_lib))
                compared += 1
        choices, learns, steps = self._selection()
        out = {"attempted": self.instances, "failed": failed,
               "sampled": compared + steps,
               "checks": {"max_rel_gap": max(gaps),
                          "lib_max_gap": max(lib_gaps),
                          "choice_mismatches": choices,
                          "learn_mismatches": learns}}
        if control:
            out["control"] = {"max_rel_gap": max(ctl, default=float("nan")),
                              "lib_max_gap": max(ctl_lib,
                                                 default=float("nan"))}
        return out

    def _selection(self):
        """(choices unlike the reference's, learned states unlike the
        reference's, selection steps compared) over the watched lanes."""
        prm = self.cfg["selectors"]
        seed = self.traffic["replay_seed"]
        ran = {}                 # (replay, t, lane rng id) -> per loop
        for rec in self.records:
            t, reqs, lt, rp, lib = rec[0], rec[3], rec[5], rec[7], rec[8]
            for i, (_, alg, cp, qid) in enumerate(reqs):
                ran.setdefault((rp, t, qid), []).append(
                    (alg, cp, float(lt[i]), float(lib[i])))
        choices = learns = steps = 0
        for rp, lanes in self.watched.items():
            for lane, key in lanes:
                kind, reward = lane.spec.selector, lane.spec.reward
                names = lane.app.loop_names
                N = self.cfg["apps"][lane.spec.app]["N"]
                cp_want = ref.chunk_param(lane.spec.chunk_mode, N,
                                          lane.system.P)
                t = 0
                while (rp, t + 1, key) in self.states:
                    got = ran.get((rp, t, id(lane.rng)), [])
                    after = self.states[(rp, t + 1, key)]
                    before = self.states[(rp, t, key)] if t else [
                        sel.initial(kind, prm, seed, nm) for nm in names]
                    for li in range(len(names)):
                        steps += 1
                        if li >= len(got) or before[li] is None:
                            choices += 1
                            learns += 1
                            continue
                        alg, cp, lt, lib = got[li]
                        if (alg, cp) != (sel.decide(kind, before[li], prm),
                                         cp_want):
                            choices += 1
                        nxt = sel.learn(kind, before[li], prm, alg, lt, lib,
                                        reward)
                        if after[li] is None or not sel.same(nxt, after[li]):
                            learns += 1
                    t += 1
        return choices, learns, steps


def _state(pol) -> dict:
    """A program selector's state, in the reference's fields."""
    if pol.name == "RandomSel":
        return {"current": pol.current, "lib": pol._lib,
                "jumped": pol._jumped, "rng": pol.rng.bit_generator.state}
    if pol.name == "ExhaustiveSel":
        return {"times": pol._times.copy(), "phase": pol._phase,
                "selected": pol._selected, "lib_sum": pol._lib_sum,
                "lib_cnt": pol._lib_cnt}
    if pol.name == "ExpertSel":
        return {"current": pol.current, "t": pol._t,
                "first": pol._first_time, "prev_time": pol._prev_time,
                "prev_lib": pol._prev_lib}
    ag = pol.agent
    return {"q": ag.q.copy(), "state": ag.state, "t": ag._t,
            "alpha": ag.alpha, "rmin": ag.reward._min,
            "rmax": ag.reward._max, "rcount": ag.reward.count}
