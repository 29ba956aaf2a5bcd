"""Counters that a device program sums while it runs.

A layer inside a compiled program (the expert layer's router, say) knows
things the host cannot see without a sync: how many token-expert pairs
it routed, how many of them landed on experts held here.  The program
that wants them opens a :func:`collect` around the model's forward while
it TRACES; a layer that finds a bag open (:func:`current`) adds traced
scalars to it; the program returns ``bag.totals()`` beside its other
outputs, and the serving engine brings them home in the ONE bundled
``device_get`` of its chunk (``ServingEngine.stats``).  No bag open, no
counting: a program that does not ask pays nothing.

``rows`` is the program's mask of the token rows that are real (a
prefill's positions below its length, a decode step's active slots), so
that padding and idle slots are not counted; ``phase`` says which
program asks ("prefill" or "decode"), for counters that only one of
them keeps.
"""
import contextlib
import threading

__all__ = ["collect", "current", "Bag"]

_OPEN = threading.local()


class Bag:
    def __init__(self, phase, rows):
        self.phase, self.rows = phase, rows
        self._sums, self._maxes = {}, {}

    def add(self, name, value):
        self._sums[name] = self._sums.get(name, 0) + value

    def max(self, name, value):
        import jax.numpy as jnp
        held = self._maxes.get(name)
        self._maxes[name] = value if held is None \
            else jnp.maximum(held, value)

    def totals(self):
        """``{"sum": {name: scalar}, "max": {name: scalar}}`` (int32),
        empty dicts where no layer counted."""
        import jax.numpy as jnp
        return {"sum": {k: jnp.asarray(v, jnp.int32)
                        for k, v in self._sums.items()},
                "max": {k: jnp.asarray(v, jnp.int32)
                        for k, v in self._maxes.items()}}


@contextlib.contextmanager
def collect(phase, rows=None):
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    bag = Bag(phase, rows)
    stack.append(bag)
    try:
        yield bag
    finally:
        stack.pop()


def current():
    """The innermost open bag of this thread, or None."""
    stack = getattr(_OPEN, "stack", None)
    return stack[-1] if stack else None

