"""The benchmark's traffic generator and key sets: reproducible from the seed,
exact per-step mixes, YCSB's zipfian skew and hash."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import datasets  # noqa: E402
from bench.generator import (INSERT, OPS, READ, SCAN, UPDATE,  # noqa: E402
                             Generator, KeyChooser, Mix, YCSB_ZETAN_099,
                             fnv_hash64, zeta)


def mix(**kw):
    base = dict(name="t", loop="closed", ops_per_step=1000,
                shares={"read": 0.5, "update": 0.5},
                request={"dist": "zipfian", "constant": 0.99})
    base.update(kw)
    m = Mix(**base)
    m.validate()
    return m


@pytest.fixture(scope="module")
def keys():
    return datasets.make_keys("osm", 50_000, 3)


def ycsb_fnvhash64(val: int) -> int:
    """YCSB's Utils.fnvhash64 as written in Java, one byte at a time."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & (2 ** 64 - 1)
    s = h - 2 ** 64 if h >= 2 ** 63 else h
    return abs(s)


@pytest.mark.parametrize("gen", ["osm", "genome"])
def test_bench_keys_reproduce_from_seed(gen):
    a = datasets.make_keys(gen, 20_000, 2 ** 31 + 11)
    b = datasets.make_keys(gen, 20_000, 2 ** 31 + 11)
    c = datasets.make_keys(gen, 20_000, 5)
    assert a.dtype == np.uint64 and a.size == 20_000
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(a[1:] > a[:-1])
    assert int(a[-1]) < (2 ** 62 if gen == "osm" else 2 ** 38)


def test_bench_zeta_and_hash_match_ycsb():
    assert zeta(10 ** 10, 0.99) == pytest.approx(YCSB_ZETAN_099, rel=1e-9)
    assert zeta(1000, 0.5) == pytest.approx(
        float(np.sum(np.arange(1, 1001) ** -0.5)))
    v = np.array([0, 1, 255, 12345, 9_999_999_999], dtype=np.int64)
    assert fnv_hash64(v).tolist() == [ycsb_fnvhash64(int(x)) for x in v]


def test_bench_zipfian_skew():
    rng = np.random.default_rng(0)
    idx = KeyChooser(1_000_000, "zipfian").draw(rng, 400_000)
    assert idx.min() >= 0 and idx.max() < 1_000_000
    _, cnt = np.unique(idx, return_counts=True)
    cnt = np.sort(cnt)[::-1]
    # item 0 of Gray et al.'s zipfian has probability 1 / zeta(n)
    assert cnt[0] / idx.size == pytest.approx(1 / YCSB_ZETAN_099, rel=0.05)
    assert cnt[1] / idx.size == pytest.approx(
        2 ** -0.99 / YCSB_ZETAN_099, rel=0.1)
    uni = KeyChooser(1_000_000, "uniform").draw(rng, 400_000)
    assert np.unique(uni).size > 1.5 * np.unique(idx).size
    late = KeyChooser(1_000_000, "latest").draw(rng, 100_000)
    assert np.mean(late > 999_000) > 0.5


def test_bench_stream_reproduces_from_seed(keys):
    m = mix()
    a = Generator(m, keys, 77).closed_steps(0, 3)
    b = Generator(m, keys, 77).closed_steps(0, 3)
    c = Generator(m, keys, 78).closed_steps(0, 3)
    for x, y in zip(a, b):
        assert np.array_equal(x.op, y.op) and np.array_equal(x.key, y.key)
        assert np.array_equal(x.arg, y.arg)
    assert not np.array_equal(a[0].key, c[0].key)


def test_bench_exact_mix_every_step(keys):
    m = mix(ops_per_step=16384)
    for st in Generator(m, keys, 1).closed_steps(0, 4):
        assert len(st) == 16384
        assert int(np.sum(st.op == READ)) == 8192
        assert int(np.sum(st.op == UPDATE)) == 8192
        loaded = np.isin(st.key, keys)
        assert loaded.all()
        assert np.all(st.arg[st.op == UPDATE] < 2 ** 62)
    m = mix(ops_per_step=1001, shares={"read": 0.95, "insert": 0.05})
    assert m.counts(1001).tolist() == [951, 0, 50, 0, 0]


def test_bench_inserts_scans_and_bursts(keys):
    m = mix(ops_per_step=400, shares={"scan": 0.9, "insert": 0.1},
            scan_length={"dist": "uniform", "min": 1, "max": 100},
            bursts=[{"at": 1, "op": "insert", "count": 300,
                     "span": [0.25, 0.5]}])
    g = Generator(m, keys, 9)
    s0, s1 = g.closed_steps(0, 2)
    assert len(s0) == 400 and len(s1) == 700
    ins = np.concatenate([s0.key[s0.op == INSERT], s1.key[s1.op == INSERT]])
    assert np.unique(ins).size == ins.size
    assert not np.isin(ins, keys).any()
    burst = s1.key[-300:]
    assert np.all(s1.op[-300:] == INSERT)
    assert burst.min() >= keys[len(keys) // 4]
    assert burst.max() <= keys[len(keys) // 2]
    lens = s0.arg[s0.op == SCAN]
    assert lens.min() >= 1 and lens.max() <= 100
    edge = Generator(mix(shares={"insert": 1.0}, insert_keys="right_edge"),
                     keys, 4).closed_steps(0, 1)[0]
    assert edge.key.min() > keys[-1] and np.all(np.diff(edge.key) > 0)


def test_bench_open_arrivals(keys):
    m = mix(loop="open", rate=20_000.0, max_ops_per_step=512,
            shares={"read": 1.0}, ops_per_step=0)
    due, ops = Generator(m, keys, 5).open_arrivals(2.0)
    assert len(ops) == due.size
    assert abs(due.size - 40_000) < 6 * 200
    assert np.all(np.diff(due) >= 0) and due[-1] <= 2.0
    assert np.all(ops.op == READ)
    due2, ops2 = Generator(m, keys, 5).open_arrivals(2.0)
    assert np.array_equal(due, due2) and np.array_equal(ops.key, ops2.key)


def test_bench_mix_validation():
    with pytest.raises(ValueError):
        mix(shares={"read": 0.5, "update": 0.4})
    with pytest.raises(ValueError):
        mix(shares={"read": 0.5, "upsert": 0.5})
    with pytest.raises(ValueError):
        mix(loop="open")
    assert set(OPS) == {"read", "update", "insert", "delete", "scan"}
