"""Plain reference of the campaign's selection methods and of SimPolicy's
decision rule, written from the paper's description and the parameters the
configuration states (``selectors`` in ``lb4omp_paper_grid.json``).

A selector's state is a plain dict; ``initial`` builds it for a loop's first
instance, ``decide`` reads the algorithm it runs next, and ``learn`` folds in
one observation (loop time, LIB) of the algorithm that ran:

* RandomSel [25]: after each instance, with probability LIB / divisor, jump
  to an algorithm drawn uniformly from the portfolio (the first instance
  always jumps);
* ExhaustiveSel [25]: one instance per algorithm in portfolio order, then
  the fastest; a LIB more than ``lib_retrigger`` off its running mean (after
  ``min_samples`` monitored instances, mean above 1 %) restarts the search;
* ExpertSel [25]: STATIC first, then the absolute fuzzy system on (LIB, 1),
  then the differential system on (relative change of the loop time, change
  of LIB) moves along the portfolio ladder; triangular memberships, min of
  the memberships fires a rule, the firing-weighted mean of the rule centres
  is the output, rounded half to even;
* QLearn / SARSA (the paper's Eqs. 9-11): a 12 x 12 table over (current
  algorithm, next algorithm); an explore-first phase walks an Eulerian
  circuit over every (state, action) pair; the three-level reward compares
  the observation with the running extrema of earlier ones; alpha decays
  after exploration.

Nothing here imports the program.
"""

from __future__ import annotations

import zlib

import numpy as np

KINDS = ("RandomSel", "ExhaustiveSel", "ExpertSel", "QLearn", "SARSA")


def explore_first(n: int, start: int) -> list:
    """Actions of the Eulerian circuit over the complete digraph with self
    loops on ``n`` nodes from ``start`` (Hierholzer; each node's out-edges
    taken highest target first)."""
    out = {u: list(range(n)) for u in range(n)}
    stack, circuit = [start], []
    while stack:
        u = stack[-1]
        if out[u]:
            stack.append(out[u].pop())
        else:
            circuit.append(stack.pop())
    circuit.reverse()
    return circuit[1:]


def region_seed(seed: int, region: str) -> int:
    """A loop's RandomSel stream seed: the replay seed folded with the
    CRC-32 of the loop's name."""
    digest = zlib.crc32(repr(region).encode("utf-8"))
    return (int(seed) * 0x9E3779B1 + digest) % (2 ** 31)


def _tri(x, a, b, c):
    if x <= a:
        return 1.0 if a == b else 0.0
    if x >= c:
        return 1.0 if b == c else 0.0
    if x < b:
        return (x - a) / (b - a) if b > a else 1.0
    return (c - x) / (c - b) if c > b else 1.0


def fuzzy(system: dict, *xs) -> float:
    mem = [{t: _tri(x, *abc) for t, abc in terms.items()}
           for terms, x in zip(system["inputs"].values(), xs)]
    num = den = 0.0
    for terms, centre in system["rules"]:
        w = min(mem[i][t] for i, t in enumerate(terms))
        num += w * centre
        den += w
    return num / den if den > 0 else 0.0


def _clip(x, n):
    return min(max(x, 0), n - 1)


def _roll(st: dict, prm: dict) -> dict:
    rng = np.random.Generator(np.random.PCG64())
    rng.bit_generator.state = st["rng"]
    if st["lib"] / prm["random"]["jump_divisor"] > rng.random():
        st["current"] = int(rng.integers(0, prm["n_actions"]))
        st["jumped"] = True
    else:
        st["jumped"] = False
    st["rng"] = rng.bit_generator.state
    return st


def initial(kind: str, prm: dict, seed: int, region: str) -> dict:
    n = prm["n_actions"]
    if kind == "RandomSel":
        rng = np.random.default_rng(region_seed(seed, region))
        return _roll({"current": 0, "lib": prm["random"]["first_lib"],
                      "jumped": False, "rng": rng.bit_generator.state}, prm)
    if kind == "ExhaustiveSel":
        return {"times": np.full(n, np.inf), "phase": 0, "selected": None,
                "lib_sum": 0.0, "lib_cnt": 0}
    if kind == "ExpertSel":
        return {"current": 0, "t": 0, "first": None, "prev_time": None,
                "prev_lib": None}
    rl = prm["rl"]
    return {"q": np.zeros((n, n)), "state": rl["initial_state"], "t": 0,
            "alpha": rl["alpha"], "rmin": float("inf"),
            "rmax": float("-inf"), "rcount": 0}


def decide(kind: str, st: dict, prm: dict) -> int:
    if kind in ("RandomSel", "ExpertSel"):
        return st["current"]
    if kind == "ExhaustiveSel":
        return st["phase"] if st["selected"] is None else st["selected"]
    walk = explore_first(prm["n_actions"], prm["rl"]["initial_state"])
    if st["t"] < len(walk):
        return walk[st["t"]]
    return int(np.argmax(st["q"][st["state"]]))


def learn(kind: str, st: dict, prm: dict, action: int, loop_time: float,
          lib: float, reward=None) -> dict:
    """The state after one instance of ``action`` observed (loop time,
    LIB); ``reward`` ("LT" or "LIB") picks the RL agents' signal."""
    n = prm["n_actions"]
    st = {k: (v.copy() if isinstance(v, np.ndarray) else v)
          for k, v in st.items()}
    if kind == "RandomSel":
        st["lib"] = float(lib)
        return _roll(st, prm)
    if kind == "ExhaustiveSel":
        ex = prm["exhaustive"]
        if st["selected"] is None:
            st["times"][action] = loop_time
            st["phase"] += 1
            if st["phase"] >= n:
                st["selected"] = int(np.argmin(st["times"]))
                st["lib_sum"] = st["lib_cnt"] = 0
            return st
        st["lib_cnt"] += 1
        st["lib_sum"] += lib
        avg = st["lib_sum"] / st["lib_cnt"]
        if (st["lib_cnt"] >= ex["min_samples"] and avg > 1.0
                and abs(lib - avg) > ex["lib_retrigger"] * avg):
            st["times"][:] = np.inf
            st["phase"] = 0
            st["selected"] = None
        return st
    if kind == "ExpertSel":
        fz = prm["expert"]
        if st["t"] == 0:
            st["first"] = loop_time
            st["current"] = _clip(round(fuzzy(fz["initial"], lib, 1.0)), n)
        else:
            dT = loop_time / max(st["prev_time"], 1e-12) - 1.0
            step = fuzzy(fz["diff"], dT, lib - st["prev_lib"])
            st["current"] = _clip(round(st["current"] + step), n)
        st["prev_time"], st["prev_lib"] = loop_time, lib
        st["t"] += 1
        return st
    rl = prm["rl"]
    r_pos, r_neu, r_neg = rl["reward_levels"]
    x = loop_time if reward == "LT" else lib
    if st["rcount"] == 0 or x <= st["rmin"]:
        r = r_pos
    elif x >= st["rmax"]:
        r = r_neg
    else:
        r = r_neu
    st["rmin"], st["rmax"] = min(st["rmin"], x), max(st["rmax"], x)
    st["rcount"] += 1
    walk = explore_first(n, rl["initial_state"])
    q, s, a = st["q"], st["state"], action
    if kind == "QLearn":
        boot = float(q[a].max())
    else:
        nxt = st["t"] + 1
        boot = float(q[a, walk[nxt] if nxt < len(walk)
                       else int(np.argmax(q[a]))])
    q[s, a] += st["alpha"] * (r + rl["gamma"] * boot - q[s, a])
    st["state"] = a
    was_learning = st["t"] < len(walk)
    st["t"] += 1
    if not was_learning and rl["alpha_decay"] > 0.0:
        if rl["decay_mode"] == "subtractive":
            st["alpha"] = max(rl["alpha_min"], st["alpha"] - rl["alpha_decay"])
        else:
            st["alpha"] = max(rl["alpha_min"],
                              st["alpha"] * (1.0 - rl["alpha_decay"]))
    return st


def same(a: dict, b: dict) -> bool:
    """Two states are the same when every field is equal, bit for bit."""
    if a.keys() != b.keys():
        return False
    for k, v in a.items():
        w = b[k]
        if isinstance(v, np.ndarray) or isinstance(w, np.ndarray):
            if not np.array_equal(np.asarray(v), np.asarray(w)):
                return False
        elif v != w:
            return False
    return True


def sim_choice(prices, threshold: float, fallback: int) -> int:
    """SimPolicy's pick among priced candidates: the first cheapest, unless
    the spread (max - min) / max is under ``threshold``, when it falls back
    to candidate ``fallback``."""
    prices = np.asarray(prices, np.float64)
    best = int(np.argmin(prices))
    lo, hi = float(prices[best]), float(prices.max())
    return fallback if (hi - lo) / max(abs(hi), 1e-12) < threshold else best
