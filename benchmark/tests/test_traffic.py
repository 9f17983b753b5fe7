"""The traffic generator: the same requests for the same seed, the stated
ranges, the stated padded lengths."""

import json

import pytest

from benchmark import spec, traffic


def mix(name):
    path = spec.ROOT / "benchmark" / "traffic" / f"{name}.json"
    out = json.loads(path.read_text())
    out.pop("rehearsal", None)
    return out


def drain(t, n):
    return [t.next_request(i % t.clients, first=i < t.clients)
            for i in range(n)]


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_same_seed_same_requests_other_seed_other_tokens(name):
    m = mix(name)
    a = drain(traffic.ServeTraffic(m, 2**31 + 17, 50257), 200)
    b = drain(traffic.ServeTraffic(m, 2**31 + 17, 50257), 200)
    c = drain(traffic.ServeTraffic(m, 2**31 + 18, 50257), 200)
    assert a == b
    assert [r[1] for r in a] != [r[1] for r in c]
    # ... but the same sizes in the same order: the seed leaves the work be
    assert [(len(r[1]), r[2]) for r in a] == [(len(r[1]), r[2]) for r in c]


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_every_seed_offers_the_same_set_of_sizes(name):
    m = mix(name)
    sizes = []
    for seed in (1, 2**31 + 5):
        t = traffic.ServeTraffic(m, seed, 50257)
        reqs = drain(t, t.clients + m["population"])[t.clients:]
        sizes.append(sorted((len(p), out) for _, p, out in reqs))
    assert sizes[0] == sizes[1]


@pytest.mark.parametrize("name,prompt_ranges,out_range,padded", [
    ("decode", [(65, 127)], (48, 144), [128]),
    ("prefill", [(641, 703), (833, 895)], (8, 24), [704, 896]),
])
def test_stated_ranges_and_padded_lengths(name, prompt_ranges, out_range,
                                          padded):
    m = mix(name)
    assert traffic.padded_lengths(m, 64) == padded
    t = traffic.ServeTraffic(m, 7, 50257)
    reqs = drain(t, t.clients + 300)
    for i, (_, prompt, out) in enumerate(reqs):
        assert any(lo <= len(prompt) <= hi for lo, hi in prompt_ranges)
        assert all(0 <= tok < 50257 for tok in prompt[:4])
        if i >= t.clients:
            assert out_range[0] <= out <= out_range[1]
        else:  # a client's first answer is cut short, never lengthened
            assert 2 <= out <= out_range[1]
        assert len(prompt) + out <= 1024
    # the clients' first requests use every prompt length of the mix
    first = {len(p) for _, p, _ in reqs[: t.clients]}
    assert first == set(traffic.prompt_lengths(m))
    # both groups of the prefill mix are offered in equal number
    pop = traffic.population(m)
    for lo, hi in prompt_ranges:
        share = sum(lo <= p <= hi for p, _ in pop) / len(pop)
        assert abs(share - 1 / len(prompt_ranges)) < 0.01


def test_training_mix_names_its_driver_and_cycle():
    m = mix("cycle8")
    assert m["driver"] == "train_dp" and m["n_batches"] == 8
    assert m["sync_every"] == 10
