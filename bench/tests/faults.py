"""Faults planted under a cell's timed path.  Each must turn ``correct``
false; ``readings.py --faults`` reads their numbers on the chip, and
``test_faults.py`` checks them on the CPU.

A fault is a context manager that takes the run's driver; it patches the
program for as long as it is open and restores it on exit.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(pairs):
    old = [(obj, name, vars(obj).get(name)) for obj, name, _ in pairs]
    for obj, name, value in pairs:
        setattr(obj, name, value)
    try:
        yield
    finally:
        for obj, name, value in old:
            if value is None:
                delattr(obj, name)
            else:
                setattr(obj, name, value)


def _selector_classes():
    from repro.core.selectors import (ExhaustivePolicy, ExpertPolicy,
                                      RandomPolicy, RLPolicy)

    return (RandomPolicy, ExhaustivePolicy, ExpertPolicy, RLPolicy)


@contextlib.contextmanager
def _device_result(driver, change):
    """Every device result of the event core passed through ``change``."""
    bk = driver.bk
    inner = bk._events_call

    def call(*args):
        out = inner(*args)
        if isinstance(out, tuple):
            return (change(np.asarray(out[0])),) + tuple(out[1:])
        return change(np.asarray(out))

    bk._events_call = call
    try:
        yield
    finally:
        del bk._events_call


def altered(driver):
    """Every makespan the device returns is 10 % high."""
    return _device_result(driver, lambda x: x * 1.1)


def half_left_out(driver):
    """The second half of each call's rows is never computed: the mean of
    the first half stands in for it."""
    def change(x):
        x = x.copy()
        h = max(1, len(x) // 2)
        x[h:] = x[:h].mean()
        return x

    return _device_result(driver, change)


def learn_unchanged(driver):
    """Every selector's learn step returns its state unchanged."""
    return _patched([(c, "feedback", lambda self, d, o: None)
                     for c in _selector_classes()])


def same_choice(driver):
    """Every selector runs the same algorithm (STATIC) whatever its state."""
    from repro.core.api import Decision

    return _patched([(c, "decide", lambda self: Decision(action=0))
                     for c in _selector_classes()])


def sim_same_choice(driver):
    """SimPolicy prices every candidate and then returns STATIC."""
    from repro.core.api import Decision

    def static(inner):
        def decide():
            inner()
            return Decision(action=0)
        return decide

    return _patched([(pol, "decide", static(pol.decide))
                     for _, pol in driver.policies.values()])


def step_changes_nothing(driver):
    """The replay step does no work."""
    from repro.sim.campaign import ReplayBatch

    pairs = [(ReplayBatch, "step", lambda self, t: None)]
    if getattr(driver, "rb", None) is not None:
        pairs.append((driver.rb, "step", lambda t: None))
    return _patched(pairs)


def context_never_moves(driver):
    """The consultation's loop context stays at its first binding."""
    return _patched([(wi, "set_context", lambda *a, **k: None)
                     for wi, _ in driver.policies.values()])


#: the faults each cell can have, by name
FAULTS = {
    "campaign_lockstep": [altered, half_left_out, learn_unchanged,
                          same_choice, step_changes_nothing],
    "consult_simpolicy": [altered, half_left_out, sim_same_choice,
                          context_never_moves],
}
