"""Percentiles, spreads, the traffic generator, and the due-time arithmetic of
the serving loop on a synthetic schedule with a stall."""

import numpy as np
import pytest

from lib import stats, traffic, weights
from lib.drivers import serve


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95 and stats.percentile(v, 50) == 50 and stats.percentile(v, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0 and stats.percentile([], 95) is None
    assert stats.percentile([1, 2, 3, 4], 95) == 4


def _record(due, submit, stamps, done, n_out):
    rec = serve.Record(traffic.Request(due, np.arange(4, dtype=np.int32), n_out))
    rec.handle, rec.submit_s, rec.stamps, rec.done_s = object(), submit, stamps, done
    rec.outcome, rec.tokens = ("ok" if done is not None else None), list(range(len(stamps)))
    return rec


def test_latency_is_taken_from_the_due_time_through_a_stall():
    # three requests due at 0, 1, 2 s; the loop stalls from 0.5 to 3.0 s, so the
    # second and third are only SENT at 3.0 s and answered at 3.5 s
    recs = [
        _record(0.0, 0.0, [0.2, 0.3, 0.4], 0.4, 3),
        _record(1.0, 3.0, [3.5, 3.6], 3.6, 2),
        _record(2.0, 3.0, [3.5, 3.7], None, 4),  # still decoding at the close
    ]
    refused = serve.Record(traffic.Request(2.5, np.arange(4, dtype=np.int32), 2))
    refused.submit_s, refused.outcome = 3.0, "refused:Overloaded"
    # a ramp request, due before the window: its wait for a first token is not
    # the window's, its tokens count only from 0 on (none here) and it finished before 0
    ramp = _record(-2.0, -2.0, [-1.0, -0.5], -0.5, 2)
    win = {"seconds": 4.0, "closed_at": 4.0, "end": 4.0, "records": [ramp] + recs + [refused], "unfinished": [recs[2]]}
    e = serve.end_to_end(win)
    assert e["sent"] == 4 and e["failed"] == 1 and e["completed_in_window"] == 2
    # TTFTs from DUE time: 0.2, 2.5, 1.5 and the window length for the refused one
    assert e["values"]["ttft_p95_ms"] == pytest.approx(4000.0)
    assert e["beside"]["ttft_p50_ms"] == pytest.approx(1500.0)
    assert e["beside"]["generator_late_max_ms"] == pytest.approx(2000.0)
    # gaps: .1 .1 | .1 | .2  -> p95 is the largest
    assert e["values"]["itl_p95_ms"] == pytest.approx(200.0)
    # every token delivered inside the window counts, finished request or not: 3 + 2 + 2 over 4 s
    assert e["values"]["serve_out_tokens_per_s"] == pytest.approx(7 / 4.0)


def test_every_seed_offers_the_same_schedule_with_other_contents():
    mix = traffic.load_mix("serve_chat")
    a = traffic.make_requests(mix, 3.0, 40.0, 32768, seed=1)
    b = traffic.make_requests(mix, 3.0, 40.0, 32768, seed=2**31 + 7)
    assert len(a) == len(b) == 120
    key = lambda r: (r.due_s, len(r.prompt), r.max_new_tokens)  # noqa: E731
    assert list(map(key, a)) == list(map(key, b))
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert 0 < a[0].due_s and a[-1].due_s < 40.0 and all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    again = traffic.make_requests(mix, 3.0, 40.0, 32768, seed=1)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s for x, y in zip(a, again))
    assert all(mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"] for r in a)


@pytest.mark.parametrize("n", [50, 100, 400])
def test_lengths_are_the_distributions_quantiles_whatever_the_count(n):
    mix = traffic.load_mix("serve_chat")
    u = mix["prompt"]
    lens = sorted(len(r.prompt) for r in traffic.make_requests(mix, n / 50.0, 50.0, 32768, seed=1))
    assert len(lens) == n and lens[n // 2] == pytest.approx(u["median"], rel=0.1)
    # log-normal(256, sigma 1) puts 1.9 % of prompts over 2048: the clipped share is that, not a draw's luck
    assert sum(x == u["max"] for x in lens) == round(0.0188 * n)
    assert stats.percentile(lens, 95) == pytest.approx(u["median"] * 2.718281828 ** (1.645 * u["sigma"]), rel=0.12)


def test_a_ramp_is_a_stratum_of_its_own_before_the_window():
    mix = traffic.load_mix("serve_chat")
    key = lambda r: (r.due_s, len(r.prompt), r.max_new_tokens)  # noqa: E731
    plain = traffic.make_requests(mix, 2.0, 40.0, 32768, seed=1)
    ramped = traffic.make_requests(mix, 2.0, 40.0, 32768, seed=1, ramp_s=10.0)
    ramp = [r for r in ramped if r.due_s < 0]
    assert len(ramp) == 20 and -10.0 < ramp[0].due_s and all(x.due_s <= y.due_s for x, y in zip(ramped, ramped[1:]))
    assert list(map(key, plain)) == list(map(key, ramped[20:]))
    # the ramp's lengths are the same distribution's quantiles too
    assert sorted(len(r.prompt) for r in ramp)[10] == pytest.approx(mix["prompt"]["median"], rel=0.15)


def test_batches_are_a_function_of_seed_and_step():
    mix = traffic.load_mix("train_2k")
    s = traffic.BatchStream(mix, 2, 32768, seed=2**31 + 5)
    ids, labels = s.get(3)
    assert ids.shape == (2, 2048) and (ids[:, 1:] == labels[:, :-1]).all()
    assert (s.get(3)[0] == ids).all() and not (s.get(4)[0] == ids).all() and not (ids[0] == ids[1]).all()


def test_weights_per_layer_equal_the_one_call():
    cfg = {"hidden_size": 16, "intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
           "vocab_size": 64, "initializer_range": 0.02, "reference": "decoder"}
    big = 2**31 + 12345
    whole = weights.all_weights(big, cfg, 2, "bfloat16")
    for i in range(2):
        one = weights.layer_weights(big, cfg, i, "bfloat16")
        assert all((np.asarray(one[k], np.float32) == np.asarray(whole["layers"][i][k], np.float32)).all() for k in one)
    top = weights.top_weights(big, cfg, "bfloat16")
    assert all((np.asarray(top[k], np.float32) == np.asarray(whole["top"][k], np.float32)).all() for k in top)
    other = weights.layer_weights(big + 1, cfg, 0, "bfloat16")
    assert not (np.asarray(other["wq"], np.float32) == np.asarray(whole["layers"][0]["wq"], np.float32)).all()
    assert float(np.asarray(whole["layers"][0]["wq"], np.float32).std()) == pytest.approx(0.02, rel=0.2)
