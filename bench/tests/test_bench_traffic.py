"""The traffic generator: deterministic per seed, the same arrivals for
every seed, and the mix's shares."""
from collections import Counter

import pytest

from bench import traffic

from .conftest import cell_of


def mix(name):
    return cell_of(name).mix


@pytest.mark.parametrize("seed", [0, 7, 2**40 + 3, -5])
def test_same_seed_same_traffic(cell_name, seed):
    m = mix(cell_name)
    assert traffic.generate(m, seed) == traffic.generate(m, seed)


def test_seeds_change_order_not_arrivals(cell_name):
    m = mix(cell_name)
    a, b = traffic.generate(m, 1), traffic.generate(m, 2)
    assert [x.interactions for x in a] != [x.interactions for x in b]
    assert sorted(x.start_s for x in a) == sorted(x.start_s for x in b)
    for j in range(int(m["interactions_per_analyst"])):
        assert sorted(x.thinks[j] for x in a) == sorted(x.thinks[j] for x in b)
    # the same scripts, dealt out and ordered differently
    def scripts(analysts):
        return sorted(sorted(map(repr, x.interactions)) for x in analysts)
    assert scripts(a) == scripts(b)


def test_due_within_covers_the_window(cell_name):
    m = mix(cell_name)
    analysts = traffic.generate(m, 3)
    scripts = traffic.due_within(analysts, 45.0)
    for a, items in zip(analysts, scripts):
        assert items == a.interactions[: len(items)]
        assert a.start_s + sum(a.thinks[: len(items) - 1]) < 45.0
        if len(items) < len(a.interactions):
            assert a.start_s + sum(a.thinks[: len(items)]) >= 45.0


def test_think_times_follow_the_prior():
    m = dict(mix("notebook.think"), analysts=400, interactions_per_analyst=1)
    thinks = sorted(a.thinks[0] for a in traffic.generate(m, 0))
    assert thinks[199] == pytest.approx(6.0, rel=0.02)  # median
    assert thinks[299] == pytest.approx(23.0, rel=0.03)  # 75th percentile


def test_templates_keep_their_shares():
    m = mix("lineitem.adhoc")
    k = int(m["analysts"])
    for j in range(3):
        counts = Counter(a.interactions[j].template for a in traffic.generate(m, 9))
        assert set(counts) == {t["name"] for t in m["templates"]}
        assert max(counts.values()) - min(counts.values()) <= 1
        assert sum(counts.values()) == k


def test_notebook_mix_shares():
    m = dict(mix("notebook.think"), analysts=64)
    counts = Counter(i.template for a in traffic.generate(m, 5) for i in a.interactions)
    total = sum(counts.values())
    for kind in m["interactions"]:
        assert counts[kind["action"][0]] / total == pytest.approx(kind["weight"], abs=0.03)


def test_parameters_resolve_to_plain_values():
    m = mix("lineitem.adhoc")
    for a in traffic.generate(m, 4)[:4]:
        for it in a.interactions:
            q6 = it.recipe[1][1] if it.template == "q6" else None
            if q6:
                year_lo, year_hi = q6[0][2], q6[1][2]
                assert year_hi - year_lo == 365
                lo, hi = q6[2][2]
                assert hi - lo == pytest.approx(0.02)
