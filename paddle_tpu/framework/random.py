"""Global RNG state.

Reference analog: paddle.seed + per-device generators
(python/paddle/framework/random.py) and the TP-determinism RNG tracker
(fleet/meta_parallel/parallel_layers/random.py). JAX randomness is functional
(explicit keys), so the framework keeps a key-splitting generator for eager
mode and a *traceable* key context for compiled steps: inside
`rng_guard(key)` every draw folds a fresh counter into the provided (possibly
traced) key — deterministic, replayable, and jit-safe.

The named-state tracker (`RNGStatesTracker`) reproduces the reference's
model-parallel seed discipline: "global" states agree across TP ranks
(e.g. residual dropout), "local" states differ per rank (e.g. attention
dropout inside a sharded region)."""
from __future__ import annotations

import contextlib
import os
import threading

import jax
import jax.numpy as jnp

__all__ = ["seed", "get_rng_state", "set_rng_state", "next_key", "rng_guard",
           "RNGStatesTracker", "get_rng_tracker", "default_seed"]

_DEFAULT_SEED = 34342423252

_prng_impl_chosen = False


def _choose_prng_impl():
    """Pick the PRNG implementation once, before the first key exists.

    TPU has no native threefry — it lowers to a long scalar ALU chain that
    measurably dominates dropout-heavy train steps (BERT-base with p=0.1
    spent ~25% of its step time generating threefry bits; the on-chip RNG
    behind 'unsafe_rbg' removes that entirely). CPU/GPU keep threefry for
    bit-exact reproducibility of existing test expectations.
    Override with FLAGS_prng_impl=threefry2x32|rbg|unsafe_rbg."""
    global _prng_impl_chosen
    if _prng_impl_chosen:
        return
    _prng_impl_chosen = True
    impl = os.environ.get("FLAGS_prng_impl", "auto")
    if impl == "auto":
        from ..core.place import on_tpu

        impl = "unsafe_rbg" if on_tpu() else "threefry2x32"
    if impl != "threefry2x32":
        jax.config.update("jax_default_prng_impl", impl)


class _RNGState(threading.local):
    def __init__(self):
        self._key = None
        self.counter = 0
        self.draws = 0
        # when set, draws fold counters into this (possibly traced) key
        self.guard_key = None
        self.guard_counter = 0
        self.deferred_prev = None

    @property
    def key(self):
        if self._key is None:
            _choose_prng_impl()
            self._key = jax.random.key(_DEFAULT_SEED)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_state = _RNGState()


def default_seed():
    return _DEFAULT_SEED


def seed(s: int):
    _choose_prng_impl()
    _state.key = jax.random.key(int(s))
    _state.counter = 0
    return s


def get_rng_state():
    return (_state.key, _state.counter)


def set_rng_state(state):
    _state.key, _state.counter = state


_DEFERRED = object()


def next_key():
    """Return a fresh PRNG key. Inside rng_guard, derives from the guard key
    (trace-safe); otherwise advances the global eager state."""
    _state.draws += 1
    if _state.guard_key is _DEFERRED:
        _materialize_deferred_guard()
    if _state.guard_key is not None:
        _state.guard_counter += 1
        return jax.random.fold_in(_state.guard_key, _state.guard_counter)
    _state.counter += 1
    return jax.random.fold_in(_state.key, _state.counter)


def _materialize_deferred_guard():
    """First draw under a deferred guard: advance the PARENT stream (the
    global state or an enclosing guard) by exactly one key and adopt it as
    this guard's key — the same derivation the dispatcher's cached
    executables use, so the i-th post-seed draw is identical whether an op
    runs its first (probe) call or a warm cached call."""
    prev_guard, prev_counter = _state.deferred_prev
    _state.guard_key, _state.guard_counter = prev_guard, prev_counter
    _state.draws -= 1          # the parent advance is not a user draw
    k = next_key()
    # propagate the parent's consumed counter back through the restore in
    # deferred_rng_guard's finally (it restores from deferred_prev)
    _state.deferred_prev = (_state.guard_key, _state.guard_counter)
    _state.guard_key = k
    _state.guard_counter = 0


@contextlib.contextmanager
def deferred_rng_guard():
    """Guard for a cache entry's first (probe) run: materializes its key
    lazily on the first draw, so ops that consume no randomness leave the
    RNG stream untouched while RNG ops derive keys exactly like the
    dispatcher's cached fast path (fold_in(parent_key, ++parent_counter)
    then per-draw fold_ins)."""
    prev = (_state.guard_key, _state.guard_counter)
    prev_deferred = getattr(_state, "deferred_prev", None)
    _state.deferred_prev = prev
    _state.guard_key = _DEFERRED
    _state.guard_counter = 0
    try:
        yield
    finally:
        _state.guard_key, _state.guard_counter = _state.deferred_prev
        _state.deferred_prev = prev_deferred


def draw_count():
    """Total next_key() draws on this thread — the dispatcher's jit cache
    probes this around an op's first (eager) run to learn whether the op
    consumes randomness and therefore needs a key threaded as a traced
    input (a baked-in constant key would freeze the op's randomness)."""
    return _state.draws


@contextlib.contextmanager
def rng_guard(key):
    """Route all framework randomness through `key` (a jax PRNG key or int
    seed, may be traced). Used by the compiled train step so dropout etc. get
    fresh per-step randomness as a function input, not baked constants."""
    if isinstance(key, int):
        _choose_prng_impl()
        key = jax.random.key(key)
    elif hasattr(key, "dtype") and not jax.dtypes.issubdtype(
        key.dtype, jax.dtypes.prng_key
    ):
        # a raw scalar (e.g. per-step seed passed into a jitted step)
        _choose_prng_impl()
        key = jax.random.key(key.astype(jnp.uint32))
    prev = (_state.guard_key, _state.guard_counter)
    _state.guard_key = key
    _state.guard_counter = 0
    try:
        yield
    finally:
        _state.guard_key, _state.guard_counter = prev


class RNGStatesTracker:
    """Named RNG streams for TP determinism (reference:
    fleet/meta_parallel/parallel_layers/random.py RNGStatesTracker)."""

    def __init__(self):
        self.states = {}

    def reset(self):
        self.states = {}

    def add(self, name, seed_):
        if name in self.states:
            raise ValueError(f"rng state {name} already exists")
        _choose_prng_impl()
        self.states[name] = (jax.random.key(int(seed_)), 0)

    @contextlib.contextmanager
    def rng_state(self, name="model-parallel-rng"):
        if name not in self.states:
            self.add(name, _DEFAULT_SEED + hash(name) % 10007)
        key, counter = self.states[name]
        prev = (_state.guard_key, _state.guard_counter)
        _state.guard_key = key
        _state.guard_counter = counter
        try:
            yield
        finally:
            self.states[name] = (key, _state.guard_counter)
            _state.guard_key, _state.guard_counter = prev


_tracker = RNGStatesTracker()


def get_rng_tracker():
    return _tracker
