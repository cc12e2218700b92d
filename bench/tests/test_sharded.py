"""The harness on several chips, on four host-emulated CPU devices: the
sharded corpus, the sharded reference, and a tiny four-shard IVF_SQ8 cell
through ``run_cell``, sound and with the ``drop_shard`` fault planted.

Each case runs its body in a child process with four CPU devices
(``multichip.py``); the ``_``-named functions are those bodies.
"""
import copy
import hashlib

import numpy as np
import pytest

from bench import run
from bench.tests import multichip

SEED = 2**32 + 29  # past 32 bits, as a run's seed may be
N, QUERIES, DIM = 20001, 300, 100  # 20,001 rows: blocks and shards that do not divide them


def _corpus(seed, shards, n=N):
    from bench import corpus

    rows, pool = corpus.make_corpus_sharded(seed, n, QUERIES, DIM, corpus.shard_mesh(shards))
    return np.asarray(rows), np.asarray(pool)


def _digest(*arrays):
    return hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes() for a in arrays)).hexdigest()


def _generator_is_deterministic():
    from bench import corpus

    rows, pool = _corpus(SEED, 4)
    again, other = _corpus(SEED, 4), _corpus(SEED + 1, 4)
    norms = np.linalg.norm(rows[:N], axis=1)
    return {"shape": list(rows.shape), "per_shard": corpus.rows_per_shard(N, 4),
            "same": _digest(rows, pool) == _digest(*again), "other": _digest(rows, pool) != _digest(*other),
            "pad_zero": bool((rows[N:] == 0).all()), "norm_err": float(np.abs(norms - 1).max())}


def test_sharded_generator_is_deterministic():
    got = multichip.call(4, "bench.tests.test_sharded:_generator_is_deterministic")
    assert got["shape"] == [4 * got["per_shard"], DIM] and got["per_shard"] % 8192 == 0
    assert got["same"] and got["other"] and got["pad_zero"]
    assert got["norm_err"] < 1e-5


def _rows_by_layout(n):
    return {shards: _digest(rows[:n], pool) for shards in (1, 2, 4) for rows, pool in [_corpus(SEED, shards, n)]}


@pytest.mark.parametrize("n", [N, 3 * 8192])
def test_shard_rows_do_not_depend_on_the_layout(n):
    digests = multichip.call(4, "bench.tests.test_sharded:_rows_by_layout", n)
    assert len(set(digests.values())) == 1, digests


def _host_copy_is_the_corpus(n, blocks_a_chunk):
    import jax

    from bench import corpus

    corpus.CHUNK_BYTES = blocks_a_chunk * corpus.BLOCK * DIM * 4
    mesh = corpus.shard_mesh(4)
    host, host_pool = corpus.host_corpus_sharded(SEED, n, QUERIES, DIM, mesh)
    left = sum(a.nbytes for a in jax.live_arrays())  # what the devices still hold
    rows, pool = _corpus(SEED, 4, n)
    return {"shape": list(host.shape), "rows": _digest(host) == _digest(rows[:n]),
            "pool": _digest(host_pool) == _digest(pool), "left": left}


@pytest.mark.parametrize(("n", "blocks_a_chunk"), [(N, 20), (3 * 4 * 8192 - 5, 2), (3 * 4 * 8192 - 5, 1)])
def test_host_copy_is_the_sharded_corpus(n, blocks_a_chunk):
    """Set-up's host copy, made a chunk at a time (here one chunk, chunks
    that overlap at a shard's end, and one block a chunk), holds the bits of
    the corpus that the check makes again, and leaves the devices empty."""
    got = multichip.call(4, "bench.tests.test_sharded:_host_copy_is_the_corpus", n, blocks_a_chunk)
    assert got["shape"] == [n, DIM] and got["rows"] and got["pool"]
    assert got["left"] < 8192 * DIM * 4


def _reference_matches_one_device():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench import corpus, reference

    rows, pool = corpus.make_corpus_sharded(SEED, N, QUERIES, DIM, corpus.shard_mesh(4))
    host = np.asarray(rows)[:N]
    one = jnp.asarray(host)
    out = {
        "ids": bool(np.array_equal(reference.exact_topk(rows, np.asarray(pool), 10, block=4096, n=N),
                                   reference.exact_topk(one, np.asarray(pool), 10, block=4096))),
        "scale": bool(np.array_equal(reference.sharded_sq8_scale(rows, N).view(np.uint32),
                                     reference.sq8_scale(host).view(np.uint32))),
        "sealed_scale": bool(np.array_equal(reference.sharded_sq8_scale(rows, 12345).view(np.uint32),
                                            reference.sq8_scale(host[:12345]).view(np.uint32))),
    }
    # every score below zero: a zero row past N would outscore them all were it not masked
    padded = np.zeros(rows.shape, np.float32)
    padded[:N] = -np.abs(host)
    queries = np.abs(np.asarray(pool))
    got = reference.exact_topk(jax.device_put(padded, NamedSharding(rows.sharding.mesh, P("shard"))),
                               queries, 10, block=3000, n=N)
    out["negative_ids"] = bool(np.array_equal(got, reference.exact_topk(jnp.asarray(padded[:N]), queries, 10,
                                                                        block=3000)))
    out["in_range"] = bool(got.max() < N)
    return out


def test_sharded_reference_matches_one_device():
    got = multichip.call(4, "bench.tests.test_sharded:_reference_matches_one_device")
    assert got == {"ids": True, "scale": True, "sealed_scale": True, "negative_ids": True, "in_range": True}


SPEC = run.load_spec()
#: a tiny four-shard IVF_SQ8 deployment at nprobe == nlist, under the cell's
#: own limits: eight sealed segments of 4,096, two a shard, and a growing tail
#: of 186 rows, searched raw at the merge's root.
#: recall_loss on the CPU: sound 0.116 to 0.124, drop_shard 0.314 to 0.327, four seeds each;
#: the limit is the cell's 0.25.
TINY = {"name": "tiny-ivf_sq8-4shards", "chips": 4, "traffic": "closed-1024q"}


def tiny_cell():
    _, config, mix = run.load_cell("glove100-sq8.closed", SPEC)
    config = copy.deepcopy(config)
    config.update(name=TINY["name"], shards=4)
    config["shape"].update(n=33000, queries=1000)
    config["index"]["nprobe"] = config["index"]["nlist"]
    entry = {**TINY, "config": config["name"]}
    return entry, run.validate_cell(entry, config, mix), {**mix, "request_queries": 64}


def _run_tiny_cell(system, seed):
    from bench import faults

    entry, config, mix = tiny_cell()
    program = None if system == "program" else faults.Faulty(run.Program(config["index"], 4), system)
    metrics = [m for m in SPEC["end_to_end"] if m["name"] in ("qps", "setup_s")]
    return run.run_cell(entry, config, mix, seed=seed, seconds=1.0, trace=False, metrics=metrics,
                        system=program)


@pytest.mark.parametrize("seed", [SEED, 7])
def test_four_shard_cell_is_correct(seed):
    result = multichip.call(4, "bench.tests.test_sharded:_run_tiny_cell", "program", seed)
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert len(result["device"]["memory_peak_bytes_per_device"]) == 4
    assert set(result["metrics"]) == {"qps", "setup_s"}


@pytest.mark.parametrize("seed", [SEED, 7])
def test_drop_shard_is_not_correct(seed):
    result = multichip.call(4, "bench.tests.test_sharded:_run_tiny_cell", "drop_shard", seed)
    assert not result["correct"], result["check"]
    assert result["check"]["recall_loss"]["value"] > result["check"]["recall_loss"]["limit"]


def _sizing(tmp):
    import contextlib
    import io
    import json

    from bench import sizing

    entry, config, _ = tiny_cell()
    config["shape"].update(n=3 * 4 * 8192 - 5)
    path = f"{tmp}/config.json"
    with open(path, "w") as f:
        json.dump(config, f)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        sizing.main(["--config", path, "--seed", str(SEED), "--check-queries", "300"])
    return [json.loads(line) for line in out.getvalue().splitlines()]


def test_sizing_runs_the_harness_data_and_check(tmp_path):
    lines = multichip.call(4, "bench.tests.test_sharded:_sizing", str(tmp_path))
    assert [line["phase"] for line in lines] == ["data", "regenerate", "regenerated_equal", "check"]
    assert lines[2]["value"] is True and lines[3]["queries"] == 300


def test_one_device_corpus_is_pinned():
    """``make_corpus`` (one device) makes today's bits: the corpus of every
    one-chip cell, as the ledger's runs had it. The digest is of the CPU's bits."""
    from bench.corpus import make_corpus

    rows, pool = make_corpus(2**32 + 3, 1000, 50, 100)
    digest = "b1bc7d083922b9b01fb1a6b99cddecc87263a4e2aac7fbf899918b5288d5ceb4"
    assert _digest(np.asarray(rows), np.asarray(pool)) == digest


def test_a_cell_on_other_chips_than_its_shards_is_refused():
    entry, config, mix = tiny_cell()
    with pytest.raises(ValueError, match="shards"):
        run.validate_cell({**entry, "chips": 1}, config, mix)
    with pytest.raises(ValueError, match="shards"):
        run.validate_cell(entry, {**config, "shards": 1}, mix)


def test_a_sharded_build_cell_is_refused():
    entry, config, _ = tiny_cell()
    with pytest.raises(ValueError, match="k-means reference"):
        run.validate_cell(entry, config, {"op": "build", "loop": "closed"})


@pytest.mark.parametrize("n", [1000, 4096, 33000, 40000, 1183514, 10_000_000])
def test_sealed_rows_follow_the_programs_plan(n):
    """The reference's reading of the system parameters, against the program's."""
    from repro.vdms.segments import plan_segments

    from bench import reference

    plan = plan_segments(n, 4096, 0.75, 0.2)
    assert reference.sealed_rows(n, 4096, 0.75) == plan.growing_start
