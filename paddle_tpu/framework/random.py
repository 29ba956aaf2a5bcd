"""RNG state.

Reference: paddle's global generator + per-device generators
(paddle/phi/core/generator.cc) and the TP rng-state tracker
(python/paddle/distributed/fleet/meta_parallel/pp_utils / get_rng_state_tracker).

TPU-native design: a single functional PRNG key chain.  Eager ops split from
a global key; traced (jit) code must NOT consume the global key at trace
time, so jitted train steps push an explicit key via ``rng_scope`` and ops
draw deterministic subkeys with ``fold_in`` counters — same code path works
eagerly and under trace.  The TP tracker (dropout determinism across
model-parallel ranks) lives in distributed/fleet and builds on ``fold_in``.
"""
import jax
from contextlib import contextmanager

# the key is built on first use, never at import: creating an array
# initialises the JAX backend, and a process that merely imported the
# package (the launcher parent, a bench parent) must not claim the chip
_STATE = {"key": None, "seed": 0}
# stack of (key, counter-list) pushed by traced step functions
_SCOPES = []


def _global_key():
    if _STATE["key"] is None:
        _STATE["key"] = jax.random.key(_STATE["seed"])
    return _STATE["key"]


def seed(s):
    _STATE["seed"] = int(s)
    _STATE["key"] = jax.random.key(int(s))
    return _STATE["key"]


def get_seed():
    return _STATE["seed"]


@contextmanager
def rng_scope(key):
    """Make ``key`` the source of randomness (used inside jitted steps)."""
    _SCOPES.append([key, 0])
    try:
        yield
    finally:
        _SCOPES.pop()


def in_rng_scope():
    return bool(_SCOPES)


def next_key():
    """Draw a fresh PRNG key (eager: split global; scoped: fold counter)."""
    from . import autograd as _ag
    if _ag._JOURNAL[0] is not None:
        # a journaled (graph-break recording) run consumed randomness:
        # replaying jitted segments would freeze the recorded key, so
        # the SOT segmenter must refuse this function
        _ag._JOURNAL[0].rng_used = True
    if _SCOPES:
        scope = _SCOPES[-1]
        scope[1] += 1
        return jax.random.fold_in(scope[0], scope[1])
    _STATE["key"], sub = jax.random.split(_global_key())
    return sub


def get_rng_state():
    return [_global_key()]


def set_rng_state(state, seed=None):
    """Restore the global key chain.  ``seed`` (optional) restores the
    recorded originating seed alongside it — a resumed run must not
    report this process's default seed in later checkpoint manifests."""
    _STATE["key"] = state[0]
    if seed is not None:
        _STATE["seed"] = int(seed)
