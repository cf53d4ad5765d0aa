"""Cross-host coworker data plane (review r4 #5).

Ref: atorch feeds preprocessed batches from coworker hosts over gRPC
into training-host shared memory (distributed.py:489,
shm_context.py:139,527). Tests here drive the real network path: a
TCP DataNodeServer, fetcher PROCESSES pulling into the real shm ring,
and a LocalCluster job where one data node feeds two trainer nodes
with master-KV discovery.
"""

import os
import time

import numpy as np
import pytest

from dlrover_tpu.data.remote_feed import (
    DataNodeServer,
    RemoteBatchFeeder,
    decode_batch,
    discover_data_nodes,
    encode_batch,
)

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


class TestWireFormat:
    def test_roundtrip_nested(self):
        batch = {
            "x": np.arange(12, dtype=np.int32).reshape(3, 4),
            "y": [np.float32(2.5), (np.ones((2,), np.float64), "tag")],
            "meta": {"n": 7, "f": 1.5, "none": None, "b": True},
        }
        out = decode_batch(encode_batch(batch))
        np.testing.assert_array_equal(out["x"], batch["x"])
        assert out["x"].dtype == np.int32
        assert float(out["y"][0]) == 2.5
        np.testing.assert_array_equal(out["y"][1][0], np.ones((2,)))
        assert out["y"][1][1] == "tag"
        assert out["meta"] == {"n": 7, "f": 1.5, "none": None, "b": True}

    def test_rejects_arbitrary_objects(self):
        class Evil:
            pass

        with pytest.raises(TypeError):
            encode_batch({"x": Evil()})

    def test_zero_dim_and_empty(self):
        batch = {"s": np.float32(3.0), "e": np.zeros((0, 4), np.int64)}
        out = decode_batch(encode_batch(batch))
        assert float(out["s"]) == 3.0
        assert out["e"].shape == (0, 4)


def _batches(n, start=0):
    for i in range(start, start + n):
        yield {"x": np.full((4, 8), i, np.int32), "i": i}


class TestServerAndFeeder:
    def test_two_consumers_partition_stream(self):
        server = DataNodeServer(_batches(20), host="127.0.0.1")
        addr = f"127.0.0.1:{server.port}"
        try:
            f1 = RemoteBatchFeeder([addr], name="rf_a")
            f2 = RemoteBatchFeeder([addr], name="rf_b")
            seen = []
            try:
                it1, it2 = iter(f1), iter(f2)
                done1 = done2 = False
                while not (done1 and done2):
                    if not done1:
                        try:
                            seen.append(next(it1)["i"])
                        except StopIteration:
                            done1 = True
                    if not done2:
                        try:
                            seen.append(next(it2)["i"])
                        except StopIteration:
                            done2 = True
            finally:
                f1.close()
                f2.close()
            # exactly-once partition of the whole stream
            assert sorted(seen) == list(range(20))
        finally:
            server.close()

    def test_batch_content_survives_the_ring(self):
        server = DataNodeServer(_batches(5), host="127.0.0.1")
        try:
            feeder = RemoteBatchFeeder(
                [f"127.0.0.1:{server.port}"], name="rf_c"
            )
            try:
                got = {b["i"]: b["x"] for b in feeder}
            finally:
                feeder.close()
            assert set(got) == set(range(5))
            for i, x in got.items():
                np.testing.assert_array_equal(
                    x, np.full((4, 8), i, np.int32)
                )
        finally:
            server.close()


class TestMasterMediatedDiscovery:
    def test_register_and_discover(self):
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.local_master import start_local_master

        master = start_local_master(node_num=1)
        try:
            client = MasterClient(
                master.addr, node_id=0, node_type="worker"
            )
            server = DataNodeServer(
                _batches(3), host="127.0.0.1", name="data0",
                master_client=client,
            )
            try:
                addrs = discover_data_nodes(client, timeout=10)
                assert addrs == [f"127.0.0.1:{server.port}"]
            finally:
                server.close()
        finally:
            master.stop()


@pytest.mark.slow
def test_data_node_feeds_two_trainer_nodes(tmp_path):
    """The review r4 #5 e2e: a dedicated data node (coworker
    preprocessors + TCP server) feeds TWO trainer nodes of a real
    LocalCluster job; trainers discover it through the master KV store
    and drain batches through their local shm rings. Every batch lands
    exactly once across the two nodes."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.testing.mock_cluster import LocalCluster

    n_batches = 24
    out = tmp_path / "rf"
    with LocalCluster(
        2,
        os.path.join(ASSETS, "remote_feed_train.py"),
        extra_args=[f"--log-dir={tmp_path / 'logs'}"],
        env={"RF_OUT": str(out)},
    ) as c:
        client = MasterClient(
            c.master.addr, node_id=99, node_type="data"
        )
        server = DataNodeServer(
            _batches(n_batches), host="127.0.0.1", name="data0",
            master_client=client,
        )
        try:
            rcs = c.wait(timeout=180)
        finally:
            server.close()
    assert all(rc == 0 for rc in rcs.values()), rcs
    counts, totals = [], []
    for rank in (0, 1):
        c_, t_ = open(f"{out}.{rank}").read().split()
        counts.append(int(c_))
        totals.append(int(t_))
    assert sum(counts) == n_batches, counts
    assert sum(totals) == sum(i * 4 * 8 for i in range(n_batches))


class TestUntrustedHeaders:
    """ADVICE r5: the peer is untrusted — header fields get the same
    skepticism as the pickle-free format itself."""

    def _tamper(self, batch, mutate):
        import json
        import struct

        payload = bytearray(encode_batch(batch))
        _LEN = struct.Struct("<Q")
        (hlen,) = _LEN.unpack_from(payload, 0)
        header = json.loads(bytes(payload[_LEN.size : _LEN.size + hlen]))
        mutate(header)
        new_header = json.dumps(header).encode()
        return (
            _LEN.pack(len(new_header))
            + new_header
            + bytes(payload[_LEN.size + hlen :])
        )

    def test_negative_dim_is_loud(self):
        batch = {"x": np.arange(8, dtype=np.int32)}

        def mutate(h):
            h["arrays"][0]["s"] = [-1]

        with pytest.raises(ValueError, match="invalid dims"):
            decode_batch(self._tamper(batch, mutate))

    def test_oversized_claim_is_loud(self):
        batch = {"x": np.arange(8, dtype=np.int32)}

        def mutate(h):
            h["arrays"][0]["s"] = [1 << 20]

        with pytest.raises(ValueError, match="payload holds"):
            decode_batch(self._tamper(batch, mutate))

    def test_object_dtype_is_loud(self):
        batch = {"x": np.arange(8, dtype=np.int32)}

        def mutate(h):
            h["arrays"][0]["d"] = "|O"

        with pytest.raises(ValueError, match="object dtype"):
            decode_batch(self._tamper(batch, mutate))

    def test_unencodable_batch_closes_stream_with_eof(self):
        """A TypeError from encode_batch must end the stream with the
        0-length EOF frame (protocol end), not an abrupt reset."""

        class Evil:
            pass

        def gen():
            yield {"x": np.ones(4, np.float32)}
            yield {"x": Evil()}  # unencodable
            yield {"x": np.zeros(4, np.float32)}  # never reached

        server = None
        try:
            server = DataNodeServer(gen(), host="127.0.0.1")
            import socket
            import struct

            _LEN = struct.Struct("<Q")
            conn = socket.create_connection(
                ("127.0.0.1", server.port), timeout=10
            )
            try:
                conn.sendall(b"GET\n")
                buf = b""
                while len(buf) < _LEN.size:
                    buf += conn.recv(4096)
                (n,) = _LEN.unpack(buf[: _LEN.size])
                while len(buf) < _LEN.size + n:
                    buf += conn.recv(65536)
                out = decode_batch(buf[_LEN.size : _LEN.size + n])
                np.testing.assert_array_equal(
                    out["x"], np.ones(4, np.float32)
                )
                # second GET hits the unencodable batch: a clean EOF
                conn.sendall(b"GET\n")
                buf = b""
                while len(buf) < _LEN.size:
                    chunk = conn.recv(4096)
                    if not chunk:
                        raise AssertionError(
                            "abrupt close instead of EOF frame"
                        )
                    buf += chunk
                (n,) = _LEN.unpack(buf[: _LEN.size])
                assert n == 0
            finally:
                conn.close()
        finally:
            if server is not None:
                server.close()
