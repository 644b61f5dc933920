"""Faults planted in the program underneath the timed path, to show that
the comparison deciding `correct` catches each. Each is a context manager
that patches the program's modules and undoes the patch on exit; build
the trainer or engine inside it.

* `unchanged_state`: the superstep returns the state it was given;
* `half_batch`: the loss leaves out half of each batch, the mean taken
  over the rest;
* `no_exchange`: the exchange between nodes (and so between chips) is
  left out, each node keeps its own model;
* `altered_token`: decode commits a token other than the one it chose.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def unchanged_state():
    import repro.launch.train as T
    real = T.make_algorithm

    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(state, *args, **kwargs):
            _, metrics = step(state, *args, **kwargs)
            return state, metrics
        return broken
    return _patch(T, "make_algorithm", make)


def half_batch():
    import repro.launch.train as T
    real = T.model_loss

    def loss(cfg, params, batch, *a, **kw):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return real(cfg, params, half, *a, **kw)
    return _patch(T, "model_loss", loss)


def no_exchange():
    from repro.core.exchange import GossipTransport

    def mix_pair(self, tree, *a, residual=None, **kw):
        if kw.get("quantize") and self.codec.carries_residual:
            return tree, residual
        return tree
    return _patch(GossipTransport, "mix_pair", mix_pair)


def altered_token():
    from repro.serve.engine import ServeEngine
    real = ServeEngine._build_fns

    class Altered:
        def __init__(self, fn, vocab):
            self.fn, self.vocab = fn, vocab

        def __call__(self, *a):
            toks, caches, pools = self.fn(*a)
            return (toks + 1) % self.vocab, caches, pools

        def _cache_size(self):
            return self.fn._cache_size()

    def build(self):
        real(self)
        self._decode = Altered(self._decode, self.cfg.vocab_size)
    return _patch(ServeEngine, "_build_fns", build)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered_token": altered_token}
