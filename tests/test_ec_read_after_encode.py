"""Read-after-encode through the client's NORMAL path on a one-server
cluster: the master's /dir/lookup answers for an EC-encoded volume from
its shard holders (reference topology.go Lookup), and a named
collection's shard / .ecx / .ecj / .vif paths carry the collection."""

import hashlib
import os

import numpy as np
import pytest

from seaweedfs_tpu.client import operation
from seaweedfs_tpu.client.wdclient import MasterClient
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell.commands import ShellContext
from seaweedfs_tpu.storage import store as store_mod
from seaweedfs_tpu.utils.httpd import HttpError, http_json


@pytest.fixture
def cluster(tmp_path):
    master = MasterServer()
    master.start()
    vs = VolumeServer([str(tmp_path)], master.url, scrub_interval_s=0)
    vs.start()
    yield master, vs, str(tmp_path)
    vs.stop()
    master.stop()


def _upload(mc, collection, n=24, seed=0):
    rng = np.random.default_rng(seed)
    files = {}
    for _ in range(n):
        data = rng.integers(0, 256, int(rng.integers(1, 40000)),
                            dtype=np.uint8).tobytes()
        files[operation.upload_data(mc, data, collection=collection).fid] \
            = hashlib.sha256(data).hexdigest()
    return files


def _read_all(master_url, files):
    mc = MasterClient(master_url)  # fresh: nothing cached from the upload
    for fid, sha in files.items():
        assert hashlib.sha256(
            operation.read_data(mc, fid)).hexdigest() == sha, fid


def test_lookup_falls_back_to_ec_shard_holders(cluster):
    master, vs, _ = cluster
    files = _upload(MasterClient(master.url), "")
    vids = {int(f.split(",")[0]) for f in files}
    sh = ShellContext(master.url, use_grpc=False)
    sh.lock()
    assert sh.ec_encode()
    for vid in vids:
        assert vs.store.find_volume(vid) is None  # the volume is gone
        got = http_json("GET", f"http://{master.url}/dir/lookup"
                               f"?volumeId={vid}")
        assert [loc["url"] for loc in got["locations"]] == [vs.url]
    _read_all(master.url, files)
    with pytest.raises(HttpError):  # an unknown vid is still a 404
        http_json("GET", f"http://{master.url}/dir/lookup?volumeId=9999")


def test_named_collection_encode_read_degrade_read(cluster):
    master, vs, d = cluster
    files = _upload(MasterClient(master.url), "b1", seed=1)
    (vid,) = {int(f.split(",")[0]) for f in files}
    sh = ShellContext(master.url, use_grpc=False)
    sh.lock()
    assert sh.ec_encode(collection="b1")
    ev = vs.store.find_ec_volume(vid)
    assert ev is not None and sorted(ev.shards) == list(range(14))
    assert ev.base_file_name == os.path.join(d, f"b1_{vid}")
    assert ev.ecx_file is not None
    assert ev.shards[3].path == os.path.join(d, f"b1_{vid}.ec03")
    _read_all(master.url, files)
    # degrade: two data shards and a parity shard, files and all
    http_json("POST", f"http://{vs.url}/admin/ec/unmount",
              {"volume_id": vid, "shard_ids": [0, 3, 11]})
    for sid in (0, 3, 11):
        os.remove(os.path.join(d, f"b1_{vid}.ec{sid:02d}"))
    http_json("POST", f"http://{vs.url}/admin/cache", {"clear": True})
    before = sum(vs.store.ec_recover_stats.values())
    _read_all(master.url, files)
    assert sum(vs.store.ec_recover_stats.values()) > before
    # ec.rebuild knows only the vid: the shards it regenerates mount
    # into the collection's volume
    plans = sh.ec_rebuild()
    assert plans and sorted(plans[0]["rebuilt"]) == [0, 3, 11], plans
    assert sorted(vs.store.find_ec_volume(vid).shards) == list(range(14))
    _read_all(master.url, files)


def test_mount_of_a_shard_with_no_file_is_an_error(cluster):
    master, vs, d = cluster
    files = _upload(MasterClient(master.url), "", n=4, seed=2)
    (vid,) = {int(f.split(",")[0]) for f in files}
    sh = ShellContext(master.url, use_grpc=False)
    sh.lock()
    assert sh.ec_encode(vid=vid)
    vs.store.unmount_ec_shards(vid, [5])
    os.remove(os.path.join(d, f"{vid}.ec05"))
    with pytest.raises(store_mod.NotFoundError, match=r"shard\(s\) \[5\]"):
        vs.store.mount_ec_shards("", vid, [4, 5])  # 4 is already mounted
    with pytest.raises(HttpError) as e:
        http_json("POST", f"http://{vs.url}/admin/ec/mount",
                  {"volume_id": vid, "shard_ids": [5]})
    assert e.value.status == 404
    # a wrong collection finds no file either, and leaves nothing behind
    with pytest.raises(store_mod.NotFoundError):
        vs.store.mount_ec_shards("nope", 777, [0])
    assert vs.store.find_ec_volume(777) is None
