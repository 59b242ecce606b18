"""Faults planted under the timed path, which ``correct`` has to catch.

Each fault takes ``patch(owner, name, value)`` (``setattr``, or pytest's
``monkeypatch.setattr``) and breaks the program's serving path in one way,
where the work is produced:

* ``alter_answers``: every served row names its neighbours one place off;
* ``half_batch``: the second half of each query batch is served the first
  half's answers (half of the batch left out);
* ``drop_inserts``: every insert is acknowledged and never applied;
* ``drop_one_insert_in_8``: every eighth write-sized insert (at most
  ``WRITE_ROWS_MAX`` rows; the bulk load's calls are larger) is
  acknowledged and never applied: a partial loss;
* ``ignore_deletes``: every delete is acknowledged and never applied;
* ``drop_exchange``: the sharded fan-out's ``all_gather`` between chips is
  left out (each chip keeps its own top-k), so the merged answer is chip
  0's alone: the sealed segments on the other chips are never searched.

``python3 -m chipbench.control --fault <name>`` reads one on the chip;
``tests/chipbench/test_faults.py`` plants each under a whole CPU run.
"""

from __future__ import annotations

import itertools

import numpy as np

WRITE_ROWS_MAX = 64


def _servable():
    from repro.serve import registry
    return registry.Servable


def _acknowledge_only(servable, n: int) -> np.ndarray:
    """Gids handed out for ``n`` rows that are never applied."""
    idx = servable.index
    out = np.arange(idx._next_gid, idx._next_gid + n, dtype=np.int32)
    idx._next_gid += n
    return out


def alter_answers(patch) -> None:
    cls = _servable()
    orig = cls._raw_query

    def broken(self, queries, k, n_probes):
        g, d = orig(self, queries, k, n_probes)
        return np.roll(g, 1, axis=1), d

    patch(cls, "_raw_query", broken)


def half_batch(patch) -> None:
    cls = _servable()
    orig = cls._raw_query

    def broken(self, queries, k, n_probes):
        g, d = orig(self, queries, k, n_probes)
        g, d = np.array(g), np.array(d)
        half = g.shape[0] // 2
        g[g.shape[0] - half:] = g[:half]
        d[d.shape[0] - half:] = d[:half]
        return g, d

    patch(cls, "_raw_query", broken)


def drop_inserts(patch) -> None:
    patch(_servable(), "insert",
          lambda self, embeddings, gids=None:
          _acknowledge_only(self, len(embeddings)))


def drop_one_insert_in_8(patch) -> None:
    cls = _servable()
    orig = cls.insert
    small = itertools.count(1)

    def broken(self, embeddings, gids=None):
        if len(embeddings) <= WRITE_ROWS_MAX and next(small) % 8 == 0:
            return _acknowledge_only(self, len(embeddings))
        return orig(self, embeddings, gids=gids)

    patch(cls, "insert", broken)


def ignore_deletes(patch) -> None:
    patch(_servable(), "delete", lambda self, gids: len(gids))


def drop_exchange(patch) -> None:
    import jax
    from repro.core import distributed

    # the sharded program is traced (and cached) on its first call: plant
    # the fault before it, and forget a program built without it
    distributed._sharded_segment_query_fn.cache_clear()
    patch(jax.lax, "all_gather",
          lambda x, axis_name, **_: x[None])


FAULTS = {f.__name__: f for f in (alter_answers, half_batch, drop_inserts,
                                  drop_one_insert_in_8, ignore_deletes,
                                  drop_exchange)}
