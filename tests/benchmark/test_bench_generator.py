"""The benchmark's traffic generator: seeded, with the mix's proportions."""
from collections import Counter

from benchmarks.chip import generator, spec

MIX = spec.traffic("decode_heavy")


def _sizes(t):
    return Counter((s.prompt_len, s.max_new) for s in t.specs)


def test_same_seed_same_requests():
    a = generator.Traffic(MIX, 2**31 + 11, 32000)
    b = generator.Traffic(MIX, 2**31 + 11, 32000)
    for _ in range(50):
        sa, sb = a.next(), b.next()
        assert sa == sb
        assert a.tokens(sa) == b.tokens(sb)


def test_seeds_share_the_sizes_in_another_order():
    a = generator.Traffic(MIX, 1, 32000)
    b = generator.Traffic(MIX, 2, 32000)
    assert sorted(s.prompt_len for s in a.specs) == sorted(
        s.prompt_len for s in b.specs)
    assert sorted(s.max_new for s in a.specs) == sorted(
        s.max_new for s in b.specs)
    assert [s.prompt_len for s in a.specs] != [s.prompt_len for s in b.specs]
    assert _sizes(a) != _sizes(b)


def test_length_classes_and_output_clipping():
    t = generator.Traffic(MIX, 5, 32000)
    n = MIX["block"]
    counts = Counter(s.prompt_len for s in t.specs[:n])
    for k, p in MIX["prompt_classes"].items():
        assert abs(counts[int(k)] - p * n) <= 1
    outs = sorted(s.max_new for s in t.specs[:n])
    lo, hi = MIX["output"]["min"], MIX["output"]["max"]
    assert lo <= outs[0] and outs[-1] <= hi
    assert outs[n // 2 - 1] <= MIX["output"]["median"] <= outs[n // 2]
    # a wider spread reaches both clips
    wide = dict(MIX["output"], sigma=3.0)
    q = generator.output_lengths(wide, n)
    assert min(q) == lo and max(q) == hi
    assert max(t.prompt_lengths) + hi <= 2048
    assert t.prompt_lengths == [128, 256, 512, 1024]


def test_token_ids_cover_the_vocabulary():
    t = generator.Traffic(MIX, 3, 100352)
    toks = [x for s in t.specs[:64] for x in t.tokens(s)]
    assert 0 <= min(toks) and max(toks) < 100352
    assert max(toks) > 90000
    assert len(t.tokens(t.specs[0])) == t.specs[0].prompt_len


def test_every_block_holds_the_same_sizes():
    t = generator.Traffic(MIX, 8, 32000)
    n = MIX["block"]
    first = Counter(s.max_new for s in t.specs[:n])
    assert all(Counter(s.max_new for s in t.specs[i:i + n]) == first
               for i in range(0, len(t.specs), n))
    assert len(t.specs) == n * MIX["blocks"]


def test_residual_lengths_of_equal_lives():
    # every request generates 10 tokens: a random step finds 1..10 left,
    # each as often; one token left is served as two
    assert generator.residual_lengths([10] * 4, 10) == [
        2, 2, 3, 4, 5, 6, 7, 8, 9, 10]
    # a life of 1 is never caught mid-flight as often as one of 3
    r = generator.residual_lengths([1, 3], 400)
    assert sum(x == 3 for x in r) == 100


def test_closed_loop_starts_in_its_steady_state():
    n = MIX["block"]
    steady = dict(MIX, start="steady")
    a = generator.Traffic(steady, 2**31 + 3, 32000, clients=n)
    b = generator.Traffic(steady, 6, 32000, clients=n)
    wave = a.specs[:n]
    assert len(a.specs) == n + n * MIX["blocks"]
    # the same first wave on every seed, in another order
    assert Counter((s.prompt_len for s in wave)) == Counter(
        s.prompt_len for s in b.specs[:n])
    assert sorted(s.max_new for s in wave) == sorted(
        s.max_new for s in b.specs[:n])
    assert [s.max_new for s in wave] != [s.max_new for s in b.specs[:n]]
    # about one request finishes at each of the first 30 steps
    mean = sum(generator.output_lengths(MIX["output"], n)) / n
    done = sum(1 for s in wave if s.max_new <= 30)
    assert abs(done - 30 * n / mean) <= 2
    assert max(s.max_new for s in wave) <= MIX["output"]["max"]
    # the blocks after the wave are the mix's own
    first = Counter(s.max_new for s in a.specs[n:2 * n])
    assert first == Counter(generator.output_lengths(MIX["output"], n))
    # an open loop, or a loop that does not start steady, has no first
    # wave: its first clients get the first block
    opened = generator.Traffic(dict(steady, loop="open", rate_per_s=1.0), 6,
                               32000, clients=n)
    assert len(opened.specs) == n * MIX["blocks"]
    plain = generator.Traffic(MIX, 6, 32000, clients=n)
    assert plain.specs == generator.Traffic(MIX, 6, 32000).specs


def test_open_loop_send_times():
    mix = dict(MIX, loop="open", rate_per_s=50.0)
    t = generator.Traffic(mix, 4, 32000)
    times = t.send_times(20.0)
    assert t.open_loop and times == t.send_times(20.0)
    assert all(0 < a < b <= 20.0 for a, b in zip(times, times[1:]))
    assert abs(len(times) - 1000) < 150
    assert not generator.Traffic(MIX, 4, 32000).open_loop


def test_stream_wraps_round_with_fresh_indices():
    mix = dict(MIX, block=4, blocks=1)
    t = generator.Traffic(mix, 9, 100)
    first = [t.next() for _ in range(4)]
    again = [t.next() for _ in range(4)]
    assert [s.prompt_len for s in first] == [s.prompt_len for s in again]
    assert len({s.index for s in first + again}) == 8
