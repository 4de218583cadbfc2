"""The generator: every seed offers the same work; a schedule seed fixes its order."""
from bench import traffic as gen

MIX = {"loop": "open", "kind": "sessions", "rate_per_s": 0.5,
       "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                         "min": 64, "max": 1024, "round_up_to": [128, 256, 512, 1024]},
       "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                         "min": 32, "max": 512}}


def _plan(mix, seed):
    return [(r.due, r.prompt_len, r.out_len) for r in gen.sessions(mix, seed, 60.0)]


def test_seeds_permute_one_set_of_sizes():
    a, b = _plan(MIX, 2**31 + 5), _plan(MIX, 7)
    assert a != b
    for k in (1, 2):
        assert sorted(x[k] for x in a) == sorted(x[k] for x in b)


def test_schedule_seed_fixes_the_order():
    mix = dict(MIX, schedule_seed=0)
    assert _plan(mix, 2**31 + 5) == _plan(mix, 7) == _plan(dict(MIX), 0)
    ticks = dict(mix, loop="open", rate_per_s=16.0)
    assert ([r.due for r in gen.tasks(ticks, 1, 10.0)]
            == [r.due for r in gen.tasks(ticks, 2, 10.0)])
    # the seed still draws the tokens
    assert (gen.token_ids(1, 0, 8, 1000) != gen.token_ids(2, 0, 8, 1000)).any()
