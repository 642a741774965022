"""The traffic generator: every seed serves the same sizes wave by wave,
in another order and with other token ids."""
import json
import os

import numpy as np
import pytest

from bench.lib import spec
from bench.lib.traffic import ClosedLoop

MIXES = ["decode_heavy"]


def _mix(name):
    return json.load(open(os.path.join(spec.BENCH, "traffic",
                                       name + ".json")))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_sizes_per_wave(name):
    mix = _mix(name)
    a, b = ClosedLoop(mix, 1, 1000), ClosedLoop(mix, 2 ** 33 + 5, 1000)
    slots = a.slots
    for wave in range(2 * a.cycle // slots):
        ra = [a.request(wave * slots + i) for i in range(slots)]
        rb = [b.request(wave * slots + i) for i in range(slots)]
        size = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)
        assert size(ra) == size(rb)
        assert max(len(r.prompt) for r in ra) == \
            a.wave_prompt_lens()[wave % len(a.wave_prompt_lens())]
        assert any(not np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(ra, rb))
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert lo <= a.prompt_lens.min() and a.prompt_lens.max() <= hi
    assert int(a.prompt_lens.max() + a.out_lens.max()) <= a.max_len


def test_same_seed_same_inputs():
    mix = _mix("decode_heavy")
    a, b = ClosedLoop(mix, 9, 500), ClosedLoop(mix, 9, 500)
    for k in (0, 31, 200):
        assert np.array_equal(a.request(k).prompt, b.request(k).prompt)
    warm = a.warm_prompts(300, 0)
    assert len(warm) == a.slots and max(map(len, warm)) == 300


def test_clients_must_fill_whole_waves():
    with pytest.raises(ValueError):
        ClosedLoop(dict(_mix("decode_heavy"), clients=20), 1, 100)



def test_a_mix_longer_than_the_cache_fails():
    with pytest.raises(ValueError, match="max_len"):
        ClosedLoop(dict(_mix("decode_heavy"), max_len=600), 1, 100)


def test_an_unknown_length_distribution_fails():
    mix = _mix("decode_heavy")
    with pytest.raises(ValueError, match="distribution"):
        ClosedLoop(dict(mix, output_tokens=dict(dist="zipf")), 1, 100)
