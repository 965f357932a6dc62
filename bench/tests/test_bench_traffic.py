import json

import numpy as np
import pytest

from bench import traffic

MIX = traffic.load_mix("chat-poisson")
RATE = traffic.rate_for(MIX, "qwen3-4b-w8a8-pallas")


def _draw(seed, seconds=40.0, rate=RATE):
    return traffic.draw(MIX, rate=rate, seed=seed, seconds=seconds,
                        vocab_size=151936)


def test_same_seed_same_draw():
    a, b = _draw(2**33 + 1), _draw(2**33 + 1)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert np.array_equal(a.output_lens, b.output_lens)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_other_seed_differs_in_order_not_in_work():
    # another seed draws other token ids; the requests' sizes, their
    # order and their due times, the window's work, stay the same
    a, b = _draw(7), _draw(8)
    assert not np.array_equal(a.prompts[0][:8], b.prompts[0][:8])
    assert np.array_equal(a.output_lens, b.output_lens)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert list(map(len, a.prompts)) == list(map(len, b.prompts))
    assert len(a) == len(b) == round(RATE * 40.0)


@pytest.mark.parametrize("seconds", [10.0, 40.0])
def test_medians_caps_and_window(seconds):
    s = _draw(3, seconds=seconds, rate=2.0)
    plen = np.asarray([len(p) for p in s.prompts])
    assert plen.max() <= 2048 and plen.min() >= 16
    assert s.output_lens.max() <= 512 and s.output_lens.min() >= 8
    assert abs(np.median(plen) - 1000) <= 60
    assert abs(np.median(s.output_lens) - 128) <= 12
    assert np.all(np.diff(s.arrivals) >= 0)
    assert s.arrivals[0] == 0.0 and s.arrivals[-1] < seconds
    assert all(p.dtype == np.int32 and p.max() < 151936 for p in s.prompts)


def test_rate_sets_count_and_mean_gap():
    s = _draw(5, seconds=100.0, rate=0.5)
    assert len(s) == 50
    assert np.mean(np.diff(s.arrivals)) == pytest.approx(2.0, rel=0.1)


def test_base_mix_and_unknown_rate(tmp_path):
    (tmp_path / "chat-poisson.json").write_text(
        json.dumps(traffic.load_mix("chat-poisson")))
    (tmp_path / "derived.json").write_text(json.dumps(
        {"base": "chat-poisson",
         "arrivals": {"kind": "poisson", "rate_per_s": {"other": 1.5}}}))
    mix = traffic.load_mix("derived", tmp_path)
    assert mix["prompt_len"] == MIX["prompt_len"]
    assert traffic.rate_for(mix, "other") == 1.5
    with pytest.raises(KeyError):
        traffic.rate_for(mix, "qwen3-4b-w8a8-pallas")
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("missing", tmp_path)
