"""The benchmark's one traffic generator: a mix file in, seeded op streams out.

A traffic mix is a JSON file under ``bench/traffic/`` (see ``Mix``). This
module turns it and a seed into numpy arrays of operations, so the timed loop
only submits and steps. Everything YCSB's core workload varies is a
parameter here: the loop (closed with a fixed number of operations per step,
or open with Poisson arrivals at a fixed rate), the shares of read, update,
insert, delete and scan, the request distribution (``uniform``,
``zipfian``, ``latest``) and its constant, the scan-length distribution,
where inserted keys go, and bursts of extra operations.

``zipfian`` is YCSB's scrambled zipfian: Gray et al.'s generator over
10^10 items, scattered over the loaded keys by YCSB's 64-bit FNV hash, so
the hot keys lie all over the key space. ``latest`` is YCSB's skewed-latest
over the loaded keys in key order, the newest being the largest (right-edge
inserts).
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

OPS = ("read", "update", "insert", "delete", "scan")
READ, UPDATE, INSERT, DELETE, SCAN = range(len(OPS))
WRITES = (UPDATE, INSERT, DELETE)
PAYLOAD_BITS = 62

# YCSB ScrambledZipfianGenerator: items drawn from a zipfian over 10^10 and
# hashed onto the key range; zeta(10^10, 0.99) is YCSB's precomputed ZETAN
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN_099 = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def zeta(n: int, theta: float) -> float:
    """sum_{i=1}^{n} i^-theta: exact to 10^6 terms, Euler-Maclaurin after."""
    m = min(int(n), 1_000_000)
    s = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n > m:
        a = 1.0 - theta
        integral = (math.log(n / m) if a == 0
                    else (n ** a - m ** a) / a)
        s += (integral + (n ** -theta - m ** -theta) / 2
              - theta / 12 * (n ** (-theta - 1) - m ** (-theta - 1)))
    return s


class Zipfian:
    """Gray et al.'s zipfian over [0, items), item 0 the most popular,
    vectorized from YCSB's ZipfianGenerator.nextLong."""

    def __init__(self, items: int, theta: float, zetan: float | None = None):
        self.items = int(items)
        self.theta = float(theta)
        self.zetan = zeta(items, theta) if zetan is None else float(zetan)
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = zeta(2, theta)
        self.eta = ((1 - (2.0 / items) ** (1 - theta))
                    / (1 - zeta2 / self.zetan))

    def draw(self, rng: np.random.Generator, m: int) -> np.ndarray:
        u = rng.random(m)
        uz = u * self.zetan
        out = (self.items * np.power(self.eta * u - self.eta + 1,
                                     self.alpha)).astype(np.int64)
        np.minimum(out, self.items - 1, out=out)
        out[uz < 1 + 0.5 ** self.theta] = 1
        out[uz < 1] = 0
        return out


def fnv_hash64(v: np.ndarray) -> np.ndarray:
    """YCSB's Utils.fnvhash64, vectorized: FNV-1 over the 8 little-endian
    bytes, then the absolute value of the signed result."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    for i in range(8):
        h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= prime
    return np.abs(h.view(np.int64)).view(np.uint64)


class KeyChooser:
    """Indices into the n loaded keys, by a request distribution."""

    def __init__(self, n: int, dist: str, constant: float = 0.99):
        self.n = int(n)
        self.dist = dist
        if dist == "zipfian":
            self._z = Zipfian(YCSB_ITEM_COUNT, constant,
                              YCSB_ZETAN_099 if constant == 0.99 else None)
        elif dist == "latest":
            self._z = Zipfian(self.n, constant)
        elif dist != "uniform":
            raise ValueError(f"unknown request distribution {dist!r}")

    def draw(self, rng: np.random.Generator, m: int) -> np.ndarray:
        if self.dist == "uniform":
            return rng.integers(0, self.n, m, dtype=np.int64)
        z = self._z.draw(rng, m)
        if self.dist == "zipfian":
            return (fnv_hash64(z) % np.uint64(self.n)).astype(np.int64)
        return self.n - 1 - np.minimum(z, self.n - 1)


@dataclasses.dataclass
class Mix:
    """One traffic mix, as its file under ``bench/traffic/`` gives it.

    loop: "closed" (every step takes ``ops_per_step`` operations, the next
        step starting when the last returns) or "open" (Poisson arrivals at
        ``rate`` per second; each step admits at most ``max_ops_per_step``
        due requests, oldest first).
    shares: {op: share} over ``OPS``; each closed step, and each open run,
        holds the exact counts these give.
    request: {"dist": "uniform" | "zipfian" | "latest", "constant": theta}.
    scan_length: {"dist": "uniform" | "zipfian", "min": a, "max": b}.
    insert_keys: "uniform" (fresh keys between the smallest and largest
        loaded key) or "right_edge" (fresh keys above the largest).
    bursts: [{"at": step or second, "op": name, "count": n,
        "span": [lo, hi]}]: ``count`` extra operations in step ``at`` of a
        closed loop (counted from the first measured step) or due at
        second ``at`` of an open one, on keys between the ``lo`` and ``hi``
        quantiles of the loaded keys.
    warmup_steps: closed loop only, steps of the mix run before the window.
    """
    name: str
    loop: str
    shares: dict
    request: dict
    ops_per_step: int = 0
    rate: float = 0.0
    max_ops_per_step: int = 0
    scan_length: dict = dataclasses.field(
        default_factory=lambda: {"dist": "uniform", "min": 1, "max": 100})
    insert_keys: str = "uniform"
    bursts: list = dataclasses.field(default_factory=list)
    warmup_steps: int = 2
    why: str = ""

    @classmethod
    def from_file(cls, path: str | Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        mix = cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                     if f.name in d})
        mix.validate()
        return mix

    def validate(self) -> None:
        if self.loop not in ("closed", "open"):
            raise ValueError(f"{self.name}: loop must be closed or open")
        if self.loop == "closed" and self.ops_per_step <= 0:
            raise ValueError(f"{self.name}: closed loop needs ops_per_step")
        if self.loop == "open" and (self.rate <= 0
                                    or self.max_ops_per_step <= 0):
            raise ValueError(f"{self.name}: open loop needs rate and "
                             f"max_ops_per_step")
        unknown = set(self.shares) - set(OPS)
        if unknown or not math.isclose(sum(self.shares.values()), 1.0):
            raise ValueError(f"{self.name}: shares over {OPS} must sum to 1")

    def counts(self, total: int) -> np.ndarray:
        """Exact op counts for ``total`` operations (largest remainders)."""
        share = np.array([self.shares.get(o, 0.0) for o in OPS])
        raw = share * total
        cnt = np.floor(raw).astype(np.int64)
        rest = total - int(cnt.sum())
        cnt[np.argsort(-(raw - cnt), kind="stable")[:rest]] += 1
        return cnt


@dataclasses.dataclass
class Ops:
    """A block of operations: op codes, keys, and an argument (payload for
    update and insert, length for scan)."""
    op: np.ndarray      # uint8
    key: np.ndarray     # uint64
    arg: np.ndarray     # uint64

    def __len__(self) -> int:
        return int(self.op.size)

    def __getitem__(self, s: slice) -> "Ops":
        return Ops(self.op[s], self.key[s], self.arg[s])

    @staticmethod
    def concat(parts: list["Ops"]) -> "Ops":
        return Ops(np.concatenate([p.op for p in parts]),
                   np.concatenate([p.key for p in parts]),
                   np.concatenate([p.arg for p in parts]))


class Generator:
    """Seeded op streams of one mix over one loaded key set."""

    def __init__(self, mix: Mix, keys: np.ndarray, seed: int):
        self.mix = mix
        self.keys = keys
        self.n = int(keys.size)
        self.rng = np.random.default_rng([int(seed) % 2 ** 64, 0x7F4A])
        req = mix.request
        self.chooser = KeyChooser(self.n, req["dist"],
                                  float(req.get("constant", 0.99)))
        sl = mix.scan_length
        self._scan = (KeyChooser(int(sl["max"]) - int(sl["min"]) + 1,
                                 "latest", float(sl.get("constant", 0.99)))
                      if sl.get("dist") == "zipfian" else None)
        self._fresh: set[int] = set()
        self._edge = int(keys[-1]) if self.n else 0

    # --------------------------------------------------------------- pieces
    def _scan_lengths(self, m: int) -> np.ndarray:
        lo, hi = int(self.mix.scan_length["min"]), int(
            self.mix.scan_length["max"])
        if self._scan is None:
            return self.rng.integers(lo, hi + 1, m).astype(np.uint64)
        # zipfian over lengths, the shortest the most popular
        z = self._scan.n - 1 - self._scan.draw(self.rng, m)
        return (lo + z).astype(np.uint64)

    def fresh_keys(self, m: int, lo_q: float = 0.0,
                   hi_q: float = 1.0) -> np.ndarray:
        """``m`` distinct keys in no loaded key and not handed out before:
        uniform between the ``lo_q`` and ``hi_q`` quantiles of the loaded
        keys, or above the largest key for right-edge inserts."""
        if self.mix.insert_keys == "right_edge" and (lo_q, hi_q) == (0, 1):
            gaps = self.rng.integers(1, 1 << 10, m).astype(np.uint64)
            out = np.uint64(self._edge) + np.cumsum(gaps, dtype=np.uint64)
            self._edge = int(out[-1]) if m else self._edge
            return out
        lo = int(self.keys[min(int(lo_q * self.n), self.n - 1)])
        hi = int(self.keys[min(int(hi_q * self.n), self.n - 1)])
        out: list[int] = []
        while len(out) < m:
            c = self.rng.integers(lo, max(hi, lo + 1), 2 * (m - len(out)) + 8,
                                  dtype=np.uint64, endpoint=True)
            i = np.minimum(np.searchsorted(self.keys, c), self.n - 1)
            for k in c[self.keys[i] != c].tolist():
                if k not in self._fresh:
                    self._fresh.add(k)
                    out.append(k)
                    if len(out) == m:
                        break
        return np.array(out, dtype=np.uint64)

    def _payloads(self, m: int) -> np.ndarray:
        return self.rng.integers(0, 1 << PAYLOAD_BITS, m, dtype=np.uint64)

    def block(self, counts: np.ndarray) -> Ops:
        """One block holding exactly ``counts[o]`` operations of each op,
        in a seeded order."""
        total = int(counts.sum())
        op = np.repeat(np.arange(len(OPS), dtype=np.uint8), counts)
        op = op[self.rng.permutation(total)]
        key = np.zeros(total, dtype=np.uint64)
        arg = np.zeros(total, dtype=np.uint64)
        chosen = (op != INSERT)
        key[chosen] = self.keys[self.chooser.draw(self.rng,
                                                  int(chosen.sum()))]
        ins = op == INSERT
        key[ins] = self.fresh_keys(int(ins.sum()))
        pay = (op == UPDATE) | ins
        arg[pay] = self._payloads(int(pay.sum()))
        sc = op == SCAN
        arg[sc] = self._scan_lengths(int(sc.sum()))
        return Ops(op, key, arg)

    def burst(self, b: dict) -> Ops:
        """The extra operations of one burst entry."""
        code = OPS.index(b["op"])
        m = int(b["count"])
        lo_q, hi_q = b.get("span", [0.0, 1.0])
        if code == INSERT:
            key = self.fresh_keys(m, lo_q, hi_q)
        else:
            lo, hi = int(lo_q * self.n), max(int(hi_q * self.n),
                                              int(lo_q * self.n) + 1)
            key = self.keys[self.rng.integers(lo, min(hi, self.n), m)]
        arg = np.zeros(m, dtype=np.uint64)
        if code in (UPDATE, INSERT):
            arg = self._payloads(m)
        elif code == SCAN:
            arg = self._scan_lengths(m)
        return Ops(np.full(m, code, dtype=np.uint8), key, arg)

    # -------------------------------------------------------------- streams
    def closed_steps(self, first: int, count: int) -> list[Ops]:
        """Steps ``first`` .. ``first + count - 1`` of a closed loop (step 0
        is the first measured one), each with the mix's exact counts plus
        its bursts."""
        per = self.mix.counts(self.mix.ops_per_step)
        out = []
        for s in range(first, first + count):
            parts = [self.block(per)]
            parts += [self.burst(b) for b in self.mix.bursts
                      if int(b["at"]) == s]
            out.append(parts[0] if len(parts) == 1 else Ops.concat(parts))
        return out

    def open_arrivals(self, seconds: float) -> tuple[np.ndarray, Ops]:
        """Poisson arrivals over ``seconds``: (due offsets in seconds,
        sorted; their operations), the mix's exact counts over the run and
        the bursts merged in at their offsets."""
        rate = float(self.mix.rate)
        expect = rate * seconds
        m = int(expect + 6 * math.sqrt(expect) + 16)
        due = np.cumsum(self.rng.exponential(1.0 / rate, m))
        m = int(np.searchsorted(due, seconds, side="right"))
        due = due[:m]
        ops = self.block(self.mix.counts(m))
        bursts = [b for b in self.mix.bursts if float(b["at"]) < seconds]
        if bursts:
            extra = [self.burst(b) for b in bursts]
            bdue = np.concatenate([np.full(len(e), float(b["at"]))
                                   for b, e in zip(bursts, extra)])
            due = np.concatenate([due, bdue])
            ops = Ops.concat([ops] + extra)
            order = np.argsort(due, kind="stable")
            due, ops = due[order], Ops(ops.op[order], ops.key[order],
                                       ops.arg[order])
        return due, ops
