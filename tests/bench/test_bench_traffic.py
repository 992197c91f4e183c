"""The traffic generator: seeded, bounded, and the same work for every seed
in another order."""
import statistics

import tiny_cells  # noqa: F401  (puts the repository root on sys.path)
from bench import harness, traffic


def _spec(name):
    return harness.load_json(harness.BENCH / "traffic" / f"{name}.json")


def test_same_seed_same_waves():
    spec = _spec("chat")
    a, b = traffic.waves(spec, 2**31 + 5, 32000), traffic.waves(spec, 2**31 + 5, 32000)
    for _ in range(2):
        assert next(a) == next(b)
    c = next(traffic.waves(spec, 2**31 + 6, 32000))
    assert c != next(traffic.waves(spec, 2**31 + 5, 32000))


def test_every_wave_holds_the_same_lengths():
    spec = _spec("chat")
    want_p = sorted(traffic.quantile_lengths(spec["prompt"], spec["wave"]))
    want_o = sorted(traffic.quantile_lengths(spec["output"], spec["wave"]))
    for seed in (0, 7, 2**33 + 1):
        gen = traffic.waves(spec, seed, 32000, first_rid=100)
        for _ in range(3):
            wave = next(gen)
            assert sorted(len(p) for _, p, _ in wave) == want_p
            assert sorted(o for _, _, o in wave) == want_o


def test_order_drawn_from_the_seed_for_every_wave():
    spec = _spec("chat")

    def orders(seed):
        gen = traffic.waves(spec, seed, 32000)
        return [[(len(p), o) for _, p, o in next(gen)] for _ in range(2)]

    a, b = orders(2**31 + 5), orders(2**31 + 6)
    assert a[0] != a[1] and a[0] != b[0] and a == orders(2**31 + 5)


def test_bounds_medians_ids():
    for name in sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json")):
        spec = _spec(name)
        wave = next(traffic.waves(spec, 3, 1000, first_rid=10))
        assert [r for r, _, _ in wave] == list(range(10, 10 + spec["wave"]))
        for key, lens in (("prompt", [len(p) for _, p, _ in wave]),
                          ("output", [o for _, _, o in wave])):
            d = spec[key]
            assert d["min"] <= min(lens) and max(lens) <= d["max"]
            assert abs(statistics.median(lens) - d["median"]) <= 0.05 * d["median"]
        assert all(0 <= t < 1000 for _, p, _ in wave for t in p)
        assert set(spec["source"]) == {"prompt", "output"}
