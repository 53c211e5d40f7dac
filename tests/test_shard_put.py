"""Direct sharded placement: a band-sharded session's assembled slab goes
from the host straight into the executor's input sharding, each device
receiving its own rows, timed by the ``sr.shard_put`` span.

The mesh half runs in a subprocess on four forced CPU host devices (the
main test process sees one); the single-device half runs here.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.engine import SRServer
from repro.models.abpn import ABPNConfig, init_abpn

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from conftest import run_py  # noqa: E402

MESH = """
import json
import jax, numpy as np
from repro.engine import SRServer
from repro.engine.server import SRServer as _S
from repro.models.abpn import ABPNConfig, init_abpn

layers = init_abpn(jax.random.PRNGKey(0), ABPNConfig(num_layers=3, feature_channels=8))
kw = dict(layers=layers, band_rows=6, vertical_policy="zero", precision="fp32",
          max_bucket=4, autotune="off")
mesh = SRServer.open("abpn_x3", mesh=(1, 4), **kw)
one = SRServer.open("abpn_x3", **kw)
rng = np.random.default_rng(5)
full = rng.random((4, 24, 16, 3), dtype=np.float32)   # one ticket, bucket 4
ragged = rng.random((3, 24, 16, 3), dtype=np.float32)  # staging buffer, bucket 4
want = [np.asarray(one.submit(x).result()) for x in (full, ragged)]

puts, host_default = [], []
put, device_put = _S._put, jax.device_put

def recording_put(entry, spans, host):
    slab = put(entry, spans, host)
    puts.append((entry, slab))
    return slab

def counting_device_put(x, *args, **kwargs):
    target = args[0] if args else kwargs.get("device")
    if isinstance(x, np.ndarray) and target is None:
        host_default.append(x.shape)
    return device_put(x, *args, **kwargs)

_S._put = staticmethod(recording_put)
jax.device_put = counting_device_put
got = [np.asarray(mesh.submit(x).result()) for x in (full, ragged)]
jax.device_put = device_put
_S._put = staticmethod(put)

out = {"bitwise": [bool(np.array_equal(g, w)) for g, w in zip(got, want)],
       "puts": len(puts), "host_default_puts": len(host_default)}
placed = []
for entry, slab in puts:
    placed.append({
        "sharded": entry.in_sharding is not None and slab.sharding == entry.in_sharding,
        "shards": sorted([s.device.id, s.index[1].start, list(s.data.shape)]
                         for s in slab.addressable_shards),
        "executor_put_noop": jax.device_put(slab, entry.in_sharding) is slab,
    })
out["placed"] = placed
sm, so = mesh.session(), one.session()
out["mesh_shard_put"] = [len(sm.spans.values("shard_put")), sm.stats().get("shard_put_p50_ms")]
out["one_shard_put"] = [len(so.spans.values("shard_put")), "shard_put_p50_ms" in so.stats()]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    r = run_py(MESH, devices=4, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("path", [0, 1], ids=["single_ticket", "staging_buffer"])
def test_sharded_hr_bitwise_single_device(mesh_run, path):
    assert mesh_run["bitwise"][path] is True


@pytest.mark.parametrize("path", [0, 1], ids=["single_ticket", "staging_buffer"])
def test_slab_arrives_in_the_executor_sharding(mesh_run, path):
    assert mesh_run["puts"] == 2
    placed = mesh_run["placed"][path]
    assert placed["sharded"] is True
    # device d holds rows 6d..6d+5 of every slot of the bucket-4 slab
    assert placed["shards"] == [[d, 6 * d, [4, 6, 16, 3]] for d in range(4)]
    assert placed["executor_put_noop"] is True


def test_no_host_slab_lands_on_the_default_device_first(mesh_run):
    assert mesh_run["host_default_puts"] == 0


def test_shard_put_span_feeds_stats_on_a_mesh_session(mesh_run):
    count, p50 = mesh_run["mesh_shard_put"]
    assert count == 2 and p50 > 0.0


def test_shard_put_absent_on_a_single_device_session(mesh_run):
    assert mesh_run["one_shard_put"] == [0, False]


def test_single_device_assembly_keeps_the_default_device():
    layers = init_abpn(jax.random.PRNGKey(0), ABPNConfig(num_layers=3, feature_channels=8))
    server = SRServer.open("abpn_x3", layers=layers, band_rows=6, vertical_policy="zero",
                           max_bucket=4, autotune="off")
    frames = np.random.default_rng(1).random((3, 24, 16, 3), dtype=np.float32)
    server.submit(frames).result()
    session = server.session()
    entry = session.cache_stats()["entries"][0]
    assert entry["bucket"] == 4
    assert all(e.in_sharding is None for e in session._cache.entries())
    assert session.spans.values("shard_put") == ()
    assert "shard_put_p50_ms" not in session.stats()
    assert session.stats()["assemble_p50_ms"] > 0.0
