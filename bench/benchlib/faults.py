"""Breakages of a streamed cell's timed path, for the self-check and the
control.  Each is a function that wraps the stream's round program
(``fn(states, backlogs, ready, *masks) -> ((states, backlogs), (batch,
app_pub, nulls))``) and breaks it underneath the harness, which then
has to read ``correct`` false.

* :func:`lose_one_message` is the control: the program as it is, with
  one guarantee the configuration states broken -- one app message of
  one sender is lost where it is published (completeness).
* :func:`state_unchanged` -- a step that returns its state unchanged.
* :func:`half_batch` -- half of each round's arrivals (the upper half of
  the senders) left out.
* :func:`altered_answer` -- one round's publish count altered where it
  is produced.
"""

from __future__ import annotations

import numpy as np


def _outputs(out):
    (states, backlogs), (batch, pub, nulls) = out
    return (states, backlogs), (np.array(batch), np.array(pub),
                                np.array(nulls))


def lose_one_message(after_round: int, sender: int = 0):
    def wrap(program):
        state = {"t": 0, "done": False}

        def fn(states, backlogs, ready, *masks):
            carry, (batch, pub, nulls) = _outputs(
                program(states, backlogs, ready, *masks))
            if (not state["done"] and state["t"] >= after_round
                    and pub[0, sender] > 0):
                pub[0, sender] -= 1
                state["done"] = True
            state["t"] += 1
            return carry, (batch, pub, nulls)
        return fn
    return wrap


def state_unchanged():
    def wrap(program):
        def fn(states, backlogs, ready, *masks):
            _, (batch, pub, nulls) = _outputs(
                program(states, backlogs, ready, *masks))
            return (states, backlogs), (np.zeros_like(batch),
                                        np.zeros_like(pub),
                                        np.zeros_like(nulls))
        return fn
    return wrap


def half_batch():
    def wrap(program):
        def fn(states, backlogs, ready, *masks):
            r = np.array(ready)
            r[:, r.shape[1] // 2:] = 0
            return program(states, backlogs, r, *masks)
        return fn
    return wrap


def altered_answer(at_round: int, sender: int = 0):
    def wrap(program):
        state = {"t": 0}

        def fn(states, backlogs, ready, *masks):
            carry, (batch, pub, nulls) = _outputs(
                program(states, backlogs, ready, *masks))
            if state["t"] == at_round:
                pub[0, sender] += 1
            state["t"] += 1
            return carry, (batch, pub, nulls)
        return fn
    return wrap
