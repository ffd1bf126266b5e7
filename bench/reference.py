"""The plain reference that decides ``correct``, and its control.

The reference is a sorted key array (every loaded key's payload is key + 1,
the paper's rule) plus a dict of acknowledged writes: payload, or None for a
delete. It imports nothing of the program. ``replay`` runs a recorded run's
steps through it in step order, under the configurations' guarantee: a
step's writes apply in submission order, then every read of the step sees
them. ``compare`` counts the answers that differ.

The control is the same reference with its key comparisons made in a lower
precision (``key_dtype``): the step a later change could be tempted to take
on a chip whose native words are 32 bits wide. Keys that round to one value
collide, so reads return a neighbour's payload.
"""
from __future__ import annotations

import numpy as np

from .generator import DELETE, INSERT, READ, SCAN, UPDATE

MISSING = np.uint64(0xFFFFFFFFFFFFFFFF)   # a read that found no key


class Reference:
    def __init__(self, keys: np.ndarray, key_dtype=None):
        self.keys = keys
        self.n = int(keys.size)
        self.dtype = None if key_dtype is None else np.dtype(key_dtype)
        self.kq = keys if self.dtype is None else keys.astype(self.dtype)
        self.writes: dict = {}
        self._wkeys: np.ndarray | None = None

    def _k(self, k):
        return k if self.dtype is None else self.dtype.type(k).item()

    def _base(self, q: np.ndarray) -> np.ndarray:
        qq = q if self.dtype is None else q.astype(self.dtype)
        i = np.minimum(np.searchsorted(self.kq, qq), max(self.n - 1, 0))
        hit = self.kq[i] == qq
        return np.where(hit, self.keys[i] + np.uint64(1), MISSING)

    def get_many(self, q: np.ndarray) -> np.ndarray:
        out = self._base(q)
        if self.writes:
            w = self.writes
            for j, k in enumerate(q.tolist()):
                kk = self._k(k)
                if kk in w:
                    out[j] = MISSING if w[kk] is None else w[kk]
        return out

    def get(self, k: int):
        kk = self._k(k)
        if kk in self.writes:
            return self.writes[kk]
        v = self._base(np.array([k], dtype=np.uint64))[0]
        return None if v == MISSING else int(v)

    def write(self, op: int, k: int, p: int) -> bool:
        kk = self._k(k)
        self._wkeys = None
        if op == DELETE:
            existed = self.get(k) is not None
            self.writes[kk] = None
            return existed
        self.writes[kk] = int(p)
        return True

    def scan(self, k: int, count: int) -> list:
        """Two-way merge of loaded keys >= k (unless written) with written
        keys >= k (unless deleted)."""
        if self.dtype is not None:
            k = int(self.keys[min(int(np.searchsorted(
                self.kq, self.dtype.type(k))), self.n - 1)])
        if self._wkeys is None:
            self._wkeys = np.sort(np.fromiter(
                (int(x) for x in self.writes), dtype=np.uint64,
                count=len(self.writes)))
        keys, wk = self.keys, self._wkeys
        i = int(np.searchsorted(keys, np.uint64(k)))
        j = int(np.searchsorted(wk, np.uint64(k)))
        out: list = []
        while len(out) < count and (i < self.n or j < wk.size):
            kb = int(keys[i]) if i < self.n else None
            kw = int(wk[j]) if j < wk.size else None
            if kw is not None and (kb is None or kw <= kb):
                i += kb == kw
                j += 1
                if self.writes[self._k(kw)] is not None:
                    out.append((kw, self.writes[self._k(kw)]))
            else:
                out.append((kb, kb + 1))
                i += 1
        return out


def answers(ref: Reference, op: np.ndarray, key: np.ndarray,
            arg: np.ndarray) -> dict:
    """One step's answers from ``ref``: writes in order, then reads."""
    w = np.flatnonzero((op == UPDATE) | (op == INSERT) | (op == DELETE))
    wr = [ref.write(int(op[i]), k, a) for i, k, a in
          zip(w.tolist(), key[w].tolist(), arg[w].tolist())]
    r = np.flatnonzero(op == READ)
    s = np.flatnonzero(op == SCAN)
    return {"writes": np.array(wr, dtype=bool),
            "reads": ref.get_many(key[r]),
            "scans": [ref.scan(k, c) for k, c in
                      zip(key[s].tolist(), arg[s].tolist())]}


def engine_answers(op: np.ndarray, reqs: list) -> tuple[dict, int]:
    """The engine's answers to one step, in ``answers``' layout, and the
    number of requests it never finished."""
    w = np.flatnonzero((op == UPDATE) | (op == INSERT) | (op == DELETE))
    r = np.flatnonzero(op == READ)
    s = np.flatnonzero(op == SCAN)
    undone = sum(1 for q in reqs if not q.done)
    reads = np.fromiter(
        (MISSING if (x := reqs[i].result) is None else x
         for i in r.tolist()), dtype=np.uint64, count=r.size)
    return {"writes": np.array([bool(reqs[i].result) for i in w.tolist()],
                               dtype=bool),
            "reads": reads,
            "scans": [list(reqs[i].result or []) for i in s.tolist()]}, undone


def compare(got: dict, want: dict) -> int:
    """Answers of one step that differ."""
    bad = int(np.sum(got["writes"] != want["writes"]))
    bad += int(np.sum(got["reads"] != want["reads"]))
    bad += sum(1 for a, b in zip(got["scans"], want["scans"])
               if [tuple(x) for x in a] != [tuple(x) for x in b])
    return bad
