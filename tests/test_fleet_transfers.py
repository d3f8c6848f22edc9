"""The fleet's host-link counters and spans, on four virtual CPU devices.

One child process (it fakes the devices) serves a small duke-like world
through the four-worker fleet and the single engine, with the profiler
recording, and reports what the fleet counted beside what it was handed:
every block the gallery accepted and every block it served back, and the
round gallery and tags of every rank dispatch.  The tests hold the
counters to those, and the fleet's trace to the single engine's."""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.abspath(os.path.join(TESTS, "..", "src"))
SHARDS = 4

CHILD = r'''
import glob, json, sys, tempfile
import jax, numpy as np
import conftest
from repro.core.policy import SearchPolicy
from repro.runtime.fleet import ShardedServingEngine
from repro.runtime.gallery import ShardedGalleryStore

shards = int(sys.argv[1])
assert len(jax.devices()) == shards, jax.devices()
puts, reads, ranks = [], [], []
real_put, real_get = ShardedGalleryStore.put, ShardedGalleryStore.get
real_rank = ShardedServingEngine._dispatch_rank_advance_seg


def put(self, cam, t, emb):
    ok = real_put(self, cam, t, emb)
    if ok:
        puts.append(list(np.shape(emb)))
    return ok


def get(self, cam, t):
    out = real_get(self, cam, t)
    if out is not None:
        reads.append(list(out.shape))
    return out


def rank(self, ps, q_feat, q_seg, mask, gallery, *tags):
    ranks.append(dict(gallery=[list(gallery.shape), gallery.dtype.itemsize],
                      tags=[[list(t.shape), t.dtype.itemsize] for t in tags],
                      shards=self.n_shards))
    return real_rank(self, ps, q_feat, q_seg, mask, gallery, *tags)


ShardedGalleryStore.put, ShardedGalleryStore.get = put, get
ShardedServingEngine._dispatch_rank_advance_seg = rank
policy = SearchPolicy(scheme="rexcam", s_thresh=.05, t_thresh=.02,
                      exit_t=60)
world = conftest.make_serving_world(n_entities=60, horizon=240, seed=1,
                                    n_queries=4)
logdir = tempfile.mkdtemp()
jax.profiler.start_trace(logdir)
try:
    # raises if the fleet's trace leaves the single engine's
    eng, _ = conftest.assert_fleet_trace_identical(world, policy, shards)
finally:
    jax.profiler.stop_trace()
spans = set()
for path in glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True):
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans.update(e.name for e in line.events
                         if e.name.startswith("rex.fleet."))
print(json.dumps(dict(
    gallery=eng.gallery.counters(), rows=eng.shard_report(),
    replicated_upload_bytes=eng.replicated_upload_bytes,
    sharded_readback_bytes=eng.sharded_readback_bytes,
    frames_processed=eng.frames_processed, cache_hits=eng.cache_hits,
    dim=int(world["feats"].shape[1]), puts=puts, reads=reads, ranks=ranks,
    spans=sorted(spans))))
'''


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}")
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, TESTS] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, "-c", CHILD, str(SHARDS)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _padded_bytes(shape, dim):
    rows = shape[0]
    return (1 << max(rows - 1, 0).bit_length()) * dim * 4


def test_owner_puts_are_the_embedded_blocks(served):
    g = served["gallery"]
    assert g["owner_puts"] == served["frames_processed"] == \
        len(served["puts"]) > 0
    assert g["owner_puts"] == g["puts"]


def test_owner_reads_are_the_cache_hits(served):
    g = served["gallery"]
    assert g["owner_reads"] == served["cache_hits"] == \
        len(served["reads"]) > 0
    assert g["owner_reads"] == g["hits"]


def test_owner_bytes_are_the_padded_blocks(served):
    g, dim = served["gallery"], served["dim"]
    assert g["owner_put_bytes"] == sum(_padded_bytes(s, dim)
                                       for s in served["puts"])
    assert g["owner_read_bytes"] == sum(_padded_bytes(s, dim)
                                        for s in served["reads"])
    # nothing was evicted, so every block put is still resident
    assert g["owner_put_bytes"] == g["bytes"]


def test_replicated_upload_is_the_round_gallery_on_every_shard(served):
    ranks = served["ranks"]
    assert ranks and all(r["shards"] == SHARDS for r in ranks)
    want = 0
    for r in ranks:
        (rows, dim), itemsize = r["gallery"]
        assert dim == served["dim"] and rows & (rows - 1) == 0
        assert [shape for shape, _ in r["tags"]] == [[rows]] * 3
        want += SHARDS * (rows * dim * itemsize
                          + sum(rows * size for _, size in r["tags"]))
    assert served["replicated_upload_bytes"] == want


def test_shard_report_carries_each_counter_per_worker(served):
    rows, g = served["rows"], served["gallery"]
    assert len(rows) == SHARDS
    for key in ("owner_puts", "owner_put_bytes", "owner_reads",
                "owner_read_bytes"):
        assert sum(r[key] for r in rows) == g[key], key
    for key in ("replicated_upload_bytes", "sharded_readback_bytes"):
        assert sum(r[key] for r in rows) == served[key] > 0, key
    # every worker receives the whole replicated gallery
    assert len({r["replicated_upload_bytes"] for r in rows}) == 1


def test_fleet_trace_identical_while_spans_record(served):
    # the child served under the profiler and held the fleet's trace to the
    # single engine's; here the spans it recorded
    assert served["spans"] == ["rex.fleet.dispatch", "rex.fleet.layout",
                               "rex.fleet.owner_put", "rex.fleet.owner_read"]
