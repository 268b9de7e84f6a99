import collections

from harness import traffic

MIX = {"clients_per_slot": 2,
       "prompt_tokens": {"median": 256, "sigma": 0.8, "min": 32,
                         "max": 1024, "menu": 16},
       "output_tokens": {"median": 512, "sigma": 0.7, "min": 256,
                         "max": 2048}}


def test_prompt_lengths_come_from_a_fixed_menu():
    menu = traffic.prompt_menu(MIX["prompt_tokens"])
    assert len(menu) == 16 and menu == sorted(menu)
    assert 32 <= menu[0] and menu[-1] <= 1024
    for seed in (1, 2**40 + 3):
        mix = traffic.ServeMix(MIX, seed, 1000)
        assert {mix.sizes(j)[0] for j in range(200)} <= set(menu)


def test_every_seed_gets_nearly_the_same_sizes_in_another_order():
    a = traffic.ServeMix(MIX, 5, 1000)
    b = traffic.ServeMix(MIX, 2**35 + 1, 1000)
    sa = [a.sizes(j) for j in range(128)]
    sb = [b.sizes(j) for j in range(128)]
    assert sa != sb
    ca = collections.Counter(p for p, _ in sa)
    cb = collections.Counter(p for p, _ in sb)
    # a shifted van der Corput prefix of 128 puts 8 points in each bin,
    # give or take one
    assert all(abs(ca[k] - cb[k]) <= 2 for k in set(ca) | set(cb))


def test_steady_population_is_one_set_in_seed_order_of_whole_pages():
    mix = dict(MIX, pool={"page_size": 128})
    a = traffic.ServeMix(mix, 5, 1000).steady_population(32)
    b = traffic.ServeMix(mix, 2**35 + 1, 1000).steady_population(32)
    assert a != b and sorted(a) == sorted(b)
    menu = traffic.prompt_menu(MIX["prompt_tokens"])
    for p, o, k in a:
        assert p in menu and 0 <= k < o
        assert k == 0 or (p + k) % 128 == 0
    # a slot holds long requests more often than they are sent
    sent = traffic.ServeMix(mix, 5, 1000)
    mean_sent = sum(sent.sizes(j)[1] for j in range(512)) / 512
    assert sum(o for _, o, _ in a) / len(a) > 1.3 * mean_sent


def test_train_rows_differ_by_step_and_repeat_by_seed():
    mix = {"batch": 2, "seq_len": 16, "zipf_a": 1.3}
    a = traffic.train_batch(mix, 3, 0, 100)
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()
    assert (traffic.train_batch(mix, 3, 0, 100)["tokens"] == a["tokens"]).all()
    assert (traffic.train_batch(mix, 3, 1, 100)["tokens"] != a["tokens"]).any()
