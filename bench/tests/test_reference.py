"""The reference's comparison, and its control, at a size a CPU holds.

The control is the plain reference put in the program's place one
precision below the configuration's (bfloat16 operands, float32
accumulation); it has to come out as not correct against each
configuration's limits, while exact answers pass them."""
import numpy as np
import pytest

from bench import data, reference, spec

CONFIGS = ["sift-128-euclidean-flat", "glove-100-angular-ivf"]
K = 10


def _cfg(name, n=6000):
    cfg = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    cfg["n_rows"] = n
    return cfg


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [3, 2**40 + 5])
def test_exact_answers_pass_and_control_fails(name, seed):
    cfg = _cfg(name)
    X = data.corpus(cfg, seed)
    Q = data.queries(cfg, seed, 300)
    metric = cfg["metric"]
    _, truth = reference.topk(Q, X, K, metric)
    # the answers a sound program gives: the exact top-k over the rows
    # as stored, scored exactly
    Xs = np.asarray(reference.stored_rows(X).astype(np.float32))
    s, i = reference.topk(Q, Xs, K, metric)
    sound = reference.compare(Q, s, i, truth, X, metric, K)
    cs, ci = reference.topk(Q, X, K, metric, control=True)
    control = reference.compare(Q, cs, ci, truth, X, metric, K)
    limit = cfg["limits"]["score_gap"]
    assert sound["score_gap"] <= limit
    assert sound["bad_rows"] == 0
    assert sound["recall_loss"] <= cfg["limits"]["recall_loss"]
    assert cfg["limits"]["recall_loss"] < 1 - cfg["guarantees"][
        "recall_at_10_min"]
    assert control["score_gap"] > limit, (control, limit)


def test_bad_rows_counts_each_fault():
    s = np.array([[3.0, 2.0, 1.0]] * 5)
    i = np.array([[0, 1, 2]] * 5)
    i[1, 2] = 1  # repeated id
    i[2, 0] = -1  # missing candidate
    s[3] = [1.0, 2.0, 3.0]  # not descending
    i[4, 1] = 99  # out of range
    assert reference.bad_rows(s, i, n=10, k=3) == 4


def test_seed_wider_than_32_bits_is_its_own_stream():
    a = data.queries(_cfg("sift-128-euclidean-flat"), 2**33 + 1, 4)
    b = data.queries(_cfg("sift-128-euclidean-flat"), 1, 4)
    c = data.queries(_cfg("sift-128-euclidean-flat"), 2**33 + 1, 4)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_corpus_does_not_depend_on_the_query_count():
    cfg = _cfg("glove-100-angular-ivf", n=500)
    x1 = np.asarray(data.corpus(cfg, 9))
    data.queries(cfg, 9, 7)
    x2 = np.asarray(data.corpus(cfg, 9))
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_allclose(np.linalg.norm(x1, axis=1), 1.0, rtol=1e-5)


def test_seed_flips_signs_and_keeps_every_inverted_list():
    from bench import system

    cfg = _cfg("glove-100-angular-ivf", n=3000)
    cfg["index"] = dict(cfg["index"], n_landmarks=16, landmark_sample=2048)
    built = {}
    for seed in (11, 2**35 + 3):
        X = data.corpus(cfg, seed)
        idx = system.build_index(cfg, X, data.dataset_keys(cfg)["build"])
        built[seed] = (np.asarray(X), np.asarray(idx._state.invlists),
                       np.asarray(idx._state.ids))
    (x1, inv1, ids1), (x2, inv2, ids2) = built.values()
    assert not np.array_equal(x1, x2)
    np.testing.assert_array_equal(np.abs(x1), np.abs(x2))
    np.testing.assert_array_equal(inv1, inv2)
    np.testing.assert_array_equal(ids1, ids2)
