"""The traffic generator: deterministic per seed, the same work for every
seed, and the distributions its traffic files state."""
import collections

import numpy as np
import pytest

from bench.generator import generate

CHAT = {"arrival": "poisson",
        "prompt": {"mean": 161, "sigma": 0.9, "min": 4, "max": 512},
        "output": {"mean": 338, "sigma": 0.9, "min": 4, "max": 511},
        "sizes_seed": 0}
BIG_SEED = 2 ** 31 + 977


def shape(reqs):
    return sorted((len(r.prompt_tokens), r.output_len) for r in reqs)


def chat(seed, spans=((0.0, 12.0), (12.0, 52.0)), rate=3.0, vocab=1000,
         spec=CHAT):
    return generate(spec, seed=seed, vocab=vocab, rate=rate, spans=spans)


def in_span(reqs, t0, t1):
    return [r for r in reqs if t0 <= r.arrival < t1]


def test_same_seed_same_requests():
    a, b = chat(BIG_SEED), chat(BIG_SEED)
    assert [(r.arrival, r.prompt_tokens, r.output_len) for r in a] == \
        [(r.arrival, r.prompt_tokens, r.output_len) for r in b]


def test_seeds_permute_the_same_work():
    """Span by span, every seed offers the same lengths and gaps."""
    a, b = chat(1), chat(2)
    for t0, t1 in ((0.0, 12.0), (12.0, 52.0)):
        sa, sb = in_span(a, t0, t1), in_span(b, t0, t1)
        assert len(sa) == len(sb) == round(3.0 * (t1 - t0))
        assert shape(sa) == shape(sb)
        gaps = lambda rs: sorted(np.round(np.diff(
            [t0] + [r.arrival for r in rs] + [t1]), 9))
        assert gaps(sa) == gaps(sb)
    assert [r.output_len for r in a] != [r.output_len for r in b]
    assert a[0].prompt_tokens != b[0].prompt_tokens


@pytest.mark.parametrize("kind", ["prompt", "output"])
def test_lognormal_lengths_match_their_parameters(kind):
    spec = dict(CHAT, **{kind: dict(CHAT[kind], max=10 ** 6, min=1)})
    reqs = chat(3, spans=[(0.0, 20000.0)], rate=1.0, vocab=50, spec=spec)
    got = np.array([len(r.prompt_tokens) if kind == "prompt" else r.output_len
                    for r in reqs], float)
    assert abs(got.mean() / CHAT[kind]["mean"] - 1) < 0.03
    assert abs(np.log(np.maximum(got, 1)).std() - 0.9) < 0.05


def test_lengths_clipped_and_tokens_in_vocab():
    reqs = chat(4, spans=[(0.0, 2000.0)], rate=1.0, vocab=97)
    p = [len(r.prompt_tokens) for r in reqs]
    o = [r.output_len for r in reqs]
    assert min(p) >= 4 and max(p) == 512 and min(o) >= 4 and max(o) == 511
    assert all(0 <= t < 97 for r in reqs for t in r.prompt_tokens)


def test_poisson_rate_and_all_at_start():
    reqs = chat(5, spans=[(2.0, 1002.0)], rate=4.0, vocab=10)
    t = np.array([r.arrival for r in reqs])
    assert len(t) == 4000 and t[0] > 2.0 and t[-1] < 1002.0
    assert np.all(np.diff(t) > 0)
    gaps = np.diff(t)
    assert abs(gaps.mean() * 4.0 - 1) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05     # exponential
    offline = dict(CHAT, arrival="all_at_start")
    reqs = generate(offline, seed=5, vocab=10, n=50)
    assert len(reqs) == 50 and {r.arrival for r in reqs} == {0.0}
    other = generate(offline, seed=BIG_SEED, vocab=10, n=50)
    assert [(len(r.prompt_tokens), r.output_len) for r in reqs] == \
        [(len(r.prompt_tokens), r.output_len) for r in other]
    assert [r.prompt_tokens for r in reqs] != [r.prompt_tokens for r in other]
    with pytest.raises(ValueError):
        generate(CHAT, seed=5, vocab=10, rate=4.0)


def test_ids_follow_arrival_order():
    reqs = chat(6, rate=2.0, vocab=10)
    assert [r.req_id for r in reqs] == list(range(len(reqs))) == \
        list(range(24 + 80))
    assert np.all(np.diff([r.arrival for r in reqs]) > 0)
    assert collections.Counter(r.model for r in reqs) == {"default": 104}
