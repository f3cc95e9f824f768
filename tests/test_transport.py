"""Native transport: crypto test vectors, reliable UDP, router contract.

Crypto primitives are checked against their published vectors
(RFC 8439 for ChaCha20-Poly1305, RFC 7748 for X25519,
draft-irtf-cfrg-xchacha for HChaCha20) — the implementation lives in
native/transport/transport.cc and must match the specs bit-for-bit.
Transport tests run real sockets on 127.0.0.1, including forced
datagram loss (retransmit path) and a genuine second process.
"""

import os
import subprocess
import sys
import time

import pytest

from crdt_tpu.net import transport as t


# ---------------------------------------------------------------------------
# crypto vectors
# ---------------------------------------------------------------------------


class TestAeadRfc8439:
    KEY = bytes(range(0x80, 0xA0))
    NONCE = bytes.fromhex("070000004041424344454647")
    AAD = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
    PT = (
        b"Ladies and Gentlemen of the class of '99: If I could offer you "
        b"only one tip for the future, sunscreen would be it."
    )
    CT = bytes.fromhex(
        "d31a8d34648e60db7b86afbc53ef7ec2"
        "a4aded51296e08fea9e2b5a736ee62d6"
        "3dbea45e8ca9671282fafb69da92728b"
        "1a71de0a9e060b2905d6a5b67ecd3b36"
        "92ddbd7f2d778b8c9803aee328091b58"
        "fab324e4fad675945585808b4831d7bc"
        "3ff4def08e4b7a9de576d26586cec64b"
        "6116"
    )
    TAG = bytes.fromhex("1ae10b594f09e26a7e902ecbd0600691")

    def test_encrypt_matches_rfc(self):
        out = t.aead_encrypt(self.KEY, self.NONCE, self.PT, aad=self.AAD)
        assert out[:-16] == self.CT
        assert out[-16:] == self.TAG

    def test_decrypt_roundtrip_and_vector(self):
        assert (
            t.aead_decrypt(self.KEY, self.NONCE, self.CT + self.TAG, aad=self.AAD)
            == self.PT
        )

    def test_tamper_detected(self):
        sealed = bytearray(self.CT + self.TAG)
        sealed[3] ^= 1
        with pytest.raises(ValueError, match="authentication"):
            t.aead_decrypt(self.KEY, self.NONCE, bytes(sealed), aad=self.AAD)

    def test_wrong_aad_detected(self):
        with pytest.raises(ValueError, match="authentication"):
            t.aead_decrypt(self.KEY, self.NONCE, self.CT + self.TAG, aad=b"x")

    def test_empty_plaintext(self):
        sealed = t.aead_encrypt(self.KEY, self.NONCE, b"", aad=b"meta")
        assert len(sealed) == 16
        assert t.aead_decrypt(self.KEY, self.NONCE, sealed, aad=b"meta") == b""


def _py_hchacha20(key: bytes, nonce: bytes) -> bytes:
    """Independent pure-Python HChaCha20 (draft-irtf-cfrg-xchacha §2.2:
    the ChaCha rounds WITHOUT the final state addition; output words
    0-3 and 12-15) — the differential oracle for the C kernel."""
    import struct

    def rotl(x, n):
        return ((x << n) | (x >> (32 - n))) & 0xFFFFFFFF

    def qr(s, a, b, c, d):
        s[a] = (s[a] + s[b]) & 0xFFFFFFFF; s[d] ^= s[a]; s[d] = rotl(s[d], 16)
        s[c] = (s[c] + s[d]) & 0xFFFFFFFF; s[b] ^= s[c]; s[b] = rotl(s[b], 12)
        s[a] = (s[a] + s[b]) & 0xFFFFFFFF; s[d] ^= s[a]; s[d] = rotl(s[d], 8)
        s[c] = (s[c] + s[d]) & 0xFFFFFFFF; s[b] ^= s[c]; s[b] = rotl(s[b], 7)

    x = [0x61707865, 0x3320646E, 0x79622D32, 0x6B206574]
    x += list(struct.unpack("<8I", key))
    x += list(struct.unpack("<4I", nonce))
    for _ in range(10):
        qr(x, 0, 4, 8, 12); qr(x, 1, 5, 9, 13)
        qr(x, 2, 6, 10, 14); qr(x, 3, 7, 11, 15)
        qr(x, 0, 5, 10, 15); qr(x, 1, 6, 11, 12)
        qr(x, 2, 7, 8, 13); qr(x, 3, 4, 9, 14)
    return struct.pack("<4I", *x[0:4]) + struct.pack("<4I", *x[12:16])


class TestHChaCha20:
    def test_draft_input_regression(self):
        # the draft-irtf-cfrg-xchacha §2.2.1 input; expected value
        # pinned from two independent implementations of the spec
        # (this C kernel and _py_hchacha20). The underlying ChaCha
        # rounds are vector-checked by TestAeadRfc8439.
        key = bytes(range(32))
        nonce = bytes.fromhex("000000090000004a0000000031415927")
        out = t.hchacha20(key, nonce)
        assert out == _py_hchacha20(key, nonce)
        assert out == bytes.fromhex(
            "82413b4227b27bfed30e42508a877d73a0f9e4d58a74a853c12ec41326d3ecdc"
        )

    def test_differential_random(self):
        for i in range(16):
            key, nonce = os.urandom(32), os.urandom(16)
            assert t.hchacha20(key, nonce) == _py_hchacha20(key, nonce)


class TestX25519Rfc7748:
    def test_vector_1(self):
        k = bytes.fromhex(
            "a546e36bf0527c9d3b16154b82465edd62144c0ac1fc5a18506a2244ba449ac4"
        )
        u = bytes.fromhex(
            "e6db6867583030db3594c1a424b15f7c726624ec26b3353b10a903a6d0ab1c4c"
        )
        assert t.x25519(k, u) == bytes.fromhex(
            "c3da55379de9c6908e94ea4df28d084f32eccf03491c71f754b4075577a28552"
        )

    def test_vector_2(self):
        k = bytes.fromhex(
            "4b66e9d4d1b4673c5ad22691957d6af5c11b6421e0ea01d42ca4169e7918ba0d"
        )
        u = bytes.fromhex(
            "e5210f12786811d3f4b7959d0538ae2c31dbe7106fc03c3efc4cd549c715a493"
        )
        assert t.x25519(k, u) == bytes.fromhex(
            "95cbde9476e8907d7aade45cb4b873f88b595a68799fa152e6f8f7647aac7957"
        )

    def test_dh_commutes_rfc_keys(self):
        # RFC 7748 §6.1 Diffie-Hellman vector
        a_priv = bytes.fromhex(
            "77076d0a7318a57d3c16c17251b26645df4c2f87ebc0992ab177fba51db92c2a"
        )
        b_priv = bytes.fromhex(
            "5dab087e624a8a4b79e17f8b83800ee66f3bb1292618b6fd1c2f8b27ff88e0eb"
        )
        a_pub, _ = t.keypair(a_priv)
        b_pub, _ = t.keypair(b_priv)
        assert a_pub == bytes.fromhex(
            "8520f0098930a754748b7ddcb43ef75a0dbf3a0d26381af4eba4a98eaa9b4e6a"
        )
        assert b_pub == bytes.fromhex(
            "de9edb7d7b7dc1b4d35b61c2ece435373f8343c85b78674dadfc7e146f882b4f"
        )
        shared = bytes.fromhex(
            "4a5d9d5ba4ce2de1728e3bf480350f25e07e21c947d19e3376f09b3c1e161742"
        )
        assert t.x25519(a_priv, b_pub) == shared
        assert t.x25519(b_priv, a_pub) == shared

    def test_low_order_point_rejected(self):
        with pytest.raises(ValueError, match="low-order"):
            t.x25519(os.urandom(32), bytes(32))

    def test_differential_vs_openssl(self):
        """Random-key agreement must match the platform's production
        X25519 (cryptography/OpenSSL) in both directions. Skips where
        the optional ``cryptography`` wheel is absent — the RFC 7748
        vectors above still pin the implementation."""
        pytest.importorskip("cryptography")
        from cryptography.hazmat.primitives.asymmetric.x25519 import (
            X25519PrivateKey,
        )
        from cryptography.hazmat.primitives.serialization import (
            Encoding, NoEncryption, PrivateFormat, PublicFormat,
        )

        for _ in range(8):
            ossl_priv = X25519PrivateKey.generate()
            ossl_pub = ossl_priv.public_key().public_bytes(
                Encoding.Raw, PublicFormat.Raw
            )
            ours_pub, ours_sec = t.keypair()
            shared_ours = t.x25519(ours_sec, ossl_pub)
            from cryptography.hazmat.primitives.asymmetric.x25519 import (
                X25519PublicKey,
            )
            shared_ossl = ossl_priv.exchange(X25519PublicKey.from_public_bytes(ours_pub))
            assert shared_ours == shared_ossl
            # and our public key derivation matches theirs
            raw = ossl_priv.private_bytes(
                Encoding.Raw, PrivateFormat.Raw, NoEncryption()
            )
            pub_from_ours, _ = t.keypair(raw)
            assert pub_from_ours == ossl_pub


class TestSecureBox:
    def test_both_directions(self):
        a_pub, a_sec = t.keypair()
        b_pub, b_sec = t.keypair()
        ab = t.SecureBox(a_sec, b_pub)
        ba = t.SecureBox(b_sec, a_pub)
        msg = b"swarm update \x00\x01" * 100
        assert ba.decrypt(ab.encrypt(msg)) == msg
        assert ab.decrypt(ba.encrypt(msg, aad=b"id"), aad=b"id") == msg

    def test_third_party_cannot_decrypt(self):
        a_pub, a_sec = t.keypair()
        b_pub, b_sec = t.keypair()
        _, eve_sec = t.keypair()
        sealed = t.SecureBox(a_sec, b_pub).encrypt(b"secret")
        with pytest.raises(ValueError):
            t.SecureBox(eve_sec, a_pub).decrypt(sealed)

    def test_nonce_randomized(self):
        a_pub, a_sec = t.keypair()
        box = t.SecureBox(a_sec, a_pub)
        assert box.encrypt(b"x") != box.encrypt(b"x")


# ---------------------------------------------------------------------------
# reliable UDP endpoint
# ---------------------------------------------------------------------------


def _pump(endpoints, *, until, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not until():
        if time.monotonic() > deadline:
            raise TimeoutError("endpoints did not settle")
        for ep in endpoints:
            ep.poll()
        time.sleep(0.002)


class TestUdpEndpoint:
    def test_small_message(self):
        with t.UdpEndpoint() as a, t.UdpEndpoint() as b:
            a.send("127.0.0.1", b.port, b"hello swarm")
            got = []
            _pump([a, b], until=lambda: got or not (got.extend(b.recv_all()) or True) or got)
            ip, port, data = got[0]
            assert data == b"hello swarm"
            assert port == a.port
            _pump([a, b], until=lambda: a.pending == 0)

    def test_large_message_fragments(self):
        payload = os.urandom(300_000)  # 250 fragments
        with t.UdpEndpoint() as a, t.UdpEndpoint() as b:
            a.send("127.0.0.1", b.port, payload)
            got = []
            _pump([a, b], until=lambda: bool(got.extend(b.recv_all()) or got))
            assert got[0][2] == payload
            _pump([a, b], until=lambda: a.pending == 0)

    def test_empty_message(self):
        with t.UdpEndpoint() as a, t.UdpEndpoint() as b:
            a.send("127.0.0.1", b.port, b"")
            got = []
            _pump([a, b], until=lambda: bool(got.extend(b.recv_all()) or got))
            assert got[0][2] == b""

    def test_delivery_under_heavy_loss(self):
        """25% of outbound datagrams dropped on BOTH sides (data and
        acks): retransmit must still deliver everything exactly once."""
        msgs = [os.urandom(5000) for _ in range(10)]
        with t.UdpEndpoint() as a, t.UdpEndpoint() as b:
            a.set_loss(250, seed=1)
            b.set_loss(250, seed=2)
            for m in msgs:
                a.send("127.0.0.1", b.port, m)
            got = []
            _pump(
                [a, b],
                until=lambda: len(got) >= len(msgs)
                or not (got.extend(b.recv_all()) or True)
                or len(got) >= len(msgs),
                timeout_s=30.0,
            )
            assert sorted(d for _, _, d in got) == sorted(msgs)
            assert a.failed == 0

    def test_duplicate_suppression(self):
        """Re-sent datagrams (lost acks) must not duplicate messages."""
        with t.UdpEndpoint() as a, t.UdpEndpoint() as b:
            b.set_loss(400, seed=7)  # b's ACKS get dropped -> a re-sends
            a.send("127.0.0.1", b.port, b"once only")
            got = []
            deadline = time.monotonic() + 20
            while a.pending and time.monotonic() < deadline:
                a.poll(), b.poll()
                got.extend(b.recv_all())
                time.sleep(0.002)
            got.extend(b.recv_all())
            assert [d for _, _, d in got] == [b"once only"]

    def test_forged_ack_cannot_suppress_retransmit(self):
        """An ack without the per-message token must not clear an
        in-flight message (ADVICE r1: msg_id-only ack matching let any
        reachable host forge acks and blackhole traffic, defeating the
        rebind challenge one layer down). The receiver here IS the
        message's destination, so the token check alone is what
        rejects the forgeries."""
        import socket
        import struct

        rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        rx.bind(("127.0.0.1", 0))
        rx.settimeout(5.0)
        try:
            with t.UdpEndpoint() as a:
                mid = a.send("127.0.0.1", rx.getsockname()[1], b"precious")
                frame, _ = rx.recvfrom(2048)
                magic, typ, msg_id, idx, cnt, real_token = struct.unpack_from(
                    "<BBIHHI", frame
                )
                assert typ == 0 and msg_id == mid & 0xFFFFFFFF
                # wrong-token acks (msg_id, idx, source all correct!)
                for bad in (0, 1, 0xFFFFFFFF, real_token ^ 1):
                    rx.sendto(
                        struct.pack("<BBIHHI", magic, 1, msg_id, idx, 0, bad),
                        ("127.0.0.1", a.port),
                    )
                for _ in range(20):
                    a.poll()
                    time.sleep(0.002)
                assert a.pending == 1, "forged ack cleared the message"
                # echoing the token from the DATA header clears it
                rx.sendto(
                    struct.pack("<BBIHHI", magic, 1, msg_id, idx, 0, real_token),
                    ("127.0.0.1", a.port),
                )
                deadline = time.monotonic() + 5
                while a.pending and time.monotonic() < deadline:
                    a.poll()
                    time.sleep(0.002)
                assert a.pending == 0
        finally:
            rx.close()


# ---------------------------------------------------------------------------
# router contract over UDP + replica convergence
# ---------------------------------------------------------------------------


from crdt_tpu.net.replica import Replica  # noqa: E402
from crdt_tpu.net.udp_router import UdpRouter, pump  # noqa: E402


def _mesh(n):
    routers = [UdpRouter() for _ in range(n)]
    for i, r in enumerate(routers):
        for other in routers[:i]:
            r.add_peer(*other.addr)
    pump(routers)
    return routers


class TestUdpRouter:
    def test_handshake_builds_full_mesh(self):
        routers = _mesh(3)
        try:
            for r in routers:
                assert len(r.peers) == 2
        finally:
            for r in routers:
                r.close()

    def test_two_replicas_converge(self):
        routers = _mesh(2)
        try:
            r1 = Replica(routers[0], topic="room", client_id=1)
            r2 = Replica(routers[1], topic="room", client_id=2)
            pump(routers)
            r1.set("users", "alice", {"role": "admin"})
            r2.set("users", "bob", {"role": "guest"})
            pump(routers)
            assert r1.c == r2.c
            assert r1.c["users"]["alice"] == {"role": "admin"}
            assert r1.c["users"]["bob"] == {"role": "guest"}
        finally:
            for r in routers:
                r.close()

    def test_late_joiner_syncs_existing_state(self):
        routers = _mesh(2)
        try:
            r1 = Replica(routers[0], topic="room", client_id=1)
            pump(routers)
            r1.set("cfg", "mode", "dark")
            r1.push("log", ["a", "b"])
            pump(routers)

            late = UdpRouter()
            routers.append(late)
            r3 = Replica(late, topic="room", client_id=3)
            late.add_peer(*routers[0].addr)
            pump(routers)
            assert r3.c == r1.c
            assert r3.c["cfg"]["mode"] == "dark"
        finally:
            for r in routers:
                r.close()

    def test_convergence_under_loss(self):
        routers = _mesh(2)
        try:
            for r in routers:
                r.endpoint.set_loss(150, seed=11)
            r1 = Replica(routers[0], topic="room", client_id=1)
            r2 = Replica(routers[1], topic="room", client_id=2)
            pump(routers, timeout_s=30.0)
            for i in range(10):
                (r1 if i % 2 else r2).set("kv", f"k{i}", i)
            pump(routers, timeout_s=30.0)
            assert r1.c == r2.c
            assert len(r1.c["kv"]) == 10
        finally:
            for r in routers:
                r.close()

    def test_malformed_hello_rejected(self):
        """A hello with a short / non-hex / uppercase pk must not
        poison the peer table (short keys would hand the native x25519
        an undersized buffer)."""
        from crdt_tpu.codec.lib0 import Encoder

        def hello(pk):
            enc = Encoder()
            enc.write_any({"pk": pk, "ack": True})
            return bytes([0]) + enc.to_bytes()

        with t.UdpEndpoint() as attacker:
            target = UdpRouter()
            try:
                for bad in ("aa", "", "zz" * 32, 123):
                    attacker.send("127.0.0.1", target.endpoint.port, hello(bad))
                deadline = time.monotonic() + 5
                while time.monotonic() < deadline and (
                    attacker.pending or target.endpoint.pending
                ):
                    attacker.poll(); target.poll()
                    time.sleep(0.002)
                target.poll()
                assert target.peers == []
                # uppercase hex of a REAL key is accepted, normalized
                pub, _ = t.keypair()
                attacker.send(
                    "127.0.0.1", target.endpoint.port, hello(pub.hex().upper())
                )
                deadline = time.monotonic() + 5
                while time.monotonic() < deadline and not target.peers:
                    attacker.poll(); target.poll()
                    time.sleep(0.002)
                assert target.peers == [pub.hex()]  # lowercase key
            finally:
                target.close()

    def test_envelope_from_unknown_peer_triggers_rehandshake(self):
        routers = _mesh(2)
        try:
            r1 = Replica(routers[0], topic="room", client_id=1)
            r2 = Replica(routers[1], topic="room", client_id=2)
            pump(routers)
            r1.set("m", "k", 1)
            pump(routers)
            # simulate a restart losing peer state on router 0
            routers[0]._peers.clear()
            r2.set("m", "k2", 2)  # arrives as envelope from unknown
            pump(routers)
            assert r1.c == r2.c
        finally:
            for r in routers:
                r.close()


class TestRebindChallenge:
    def test_spoofed_hello_does_not_reroute(self):
        """An attacker replaying a victim's public key from its own
        address must not capture the victim's traffic."""
        routers = _mesh(2)
        a, b = routers
        try:
            r1 = Replica(a, topic="room", client_id=1)
            r2 = Replica(b, topic="room", client_id=2)
            pump(routers)
            victim_addr = a._peers[b.public_key].addr

            from crdt_tpu.codec.lib0 import Encoder

            enc = Encoder()
            enc.write_any({"pk": b.public_key, "ack": True})
            with t.UdpEndpoint() as attacker:
                attacker.send("127.0.0.1", a.endpoint.port, bytes([0]) + enc.to_bytes())
                deadline = time.monotonic() + 3
                while time.monotonic() < deadline and (
                    attacker.pending or a.endpoint.pending
                ):
                    attacker.poll(); a.poll(); b.poll()
                    time.sleep(0.002)
                # the attacker cannot answer the encrypted challenge:
                # the peer's address must be unchanged
                assert a._peers[b.public_key].addr == victim_addr
            r1.set("m", "k", 1)
            pump(routers)
            assert r2.c == r1.c  # traffic still reaches the real peer
        finally:
            for r in routers:
                r.close()

    def test_genuine_restart_reroutes_after_proof(self):
        """The same identity (seeded keypair) rebinding to a new port
        passes the challenge and traffic follows it."""
        seed_b = os.urandom(32)
        a = UdpRouter()
        b1 = UdpRouter(seed=seed_b)
        b1.add_peer(*a.addr)
        pump([a, b1])
        r_a = Replica(a, topic="room", client_id=1)
        r_b1 = Replica(b1, topic="room", client_id=2)
        pump([a, b1])
        r_a.set("m", "k", 1)
        pump([a, b1])
        assert r_b1.c == r_a.c
        old_addr = a._peers[b1.public_key].addr
        b1.close()

        b2 = UdpRouter(seed=seed_b)  # same identity, fresh port
        try:
            assert b2.public_key == b1.public_key
            r_b2 = Replica(b2, topic="room", client_id=3)
            b2.add_peer(*a.addr)
            pump([a, b2], timeout_s=15.0)
            assert a._peers[b2.public_key].addr == b2.addr
            assert a._peers[b2.public_key].addr != old_addr
            r_a.set("m", "k2", 2)
            pump([a, b2], timeout_s=15.0)
            assert r_b2.c["m"] == r_a.c["m"]
        finally:
            a.close()
            b2.close()


    def test_same_port_restart_resets_topic_watermark(self):
        """A restarted process on the SAME address announces from v=1
        again; the old incarnation's higher watermark must not make
        its announcements look like stale retransmits."""
        seed_b = os.urandom(32)
        a = UdpRouter()
        b1 = UdpRouter(seed=seed_b)
        port_b = b1.endpoint.port
        b1.add_peer(*a.addr)
        pump([a, b1])
        # inflate b1's announcement version past the next incarnation's
        for topic in ("t1", "t2", "t3"):
            b1.alow(topic, lambda m, f: None)
        b1.unsubscribe("t1")
        pump([a, b1])
        assert a._peers[b1.public_key].topics_v >= 4
        b1.close()

        b2 = UdpRouter(seed=seed_b, port=port_b)  # same identity+address
        try:
            r_b2 = Replica(b2, topic="room", client_id=5)  # announces v=1
            b2.add_peer(*a.addr)
            pump([a, b2], timeout_s=15.0)
            assert "room" in a._peers[b2.public_key].topics
            r_a = Replica(a, topic="room", client_id=6)
            pump([a, b2], timeout_s=15.0)
            r_a.set("m", "k", 1)
            pump([a, b2], timeout_s=15.0)
            assert r_b2.c == r_a.c
        finally:
            a.close()
            b2.close()


_CHILD = r"""
import sys, time
sys.path.insert(0, "@REPO@")
from crdt_tpu.net.replica import Replica
from crdt_tpu.net.udp_router import UdpRouter, pump

parent_ip, parent_port = sys.argv[1], int(sys.argv[2])
router = UdpRouter()
rep = Replica(router, topic="xproc", client_id=77)
router.add_peer(parent_ip, parent_port)
deadline = time.monotonic() + 15
while time.monotonic() < deadline:
    router.poll()
    if rep.c.get("handshake", {}).get("from_parent") == "hi":
        rep.set("handshake", "from_child", "hello back")
        break
    time.sleep(0.002)
else:
    sys.exit(3)
# keep pumping until the parent has surely received our write
end = time.monotonic() + 5
while time.monotonic() < end and router.endpoint.pending:
    router.poll()
    time.sleep(0.002)
sys.exit(0)
"""


class TestCrossProcess:
    def test_two_os_processes_converge(self, tmp_path):
        """A real second interpreter over real sockets — the closest
        in-tree stand-in for the reference's two-machine swarm."""
        repo = str(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        script = tmp_path / "child.py"
        script.write_text(_CHILD.replace("@REPO@", repo))

        router = UdpRouter()
        rep = Replica(router, topic="xproc", client_id=1)
        rep.set("handshake", "from_parent", "hi")

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.Popen(
            [sys.executable, str(script), "127.0.0.1", str(router.endpoint.port)],
            env=env,
        )
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                router.poll()
                if rep.c.get("handshake", {}).get("from_child") == "hello back":
                    break
                time.sleep(0.005)
            assert rep.c["handshake"]["from_child"] == "hello back"
            assert child.wait(timeout=15) == 0
        finally:
            if child.poll() is None:
                child.kill()
            router.close()


class TestTopicsReplay:
    def test_replayed_announcement_from_dead_incarnation_ignored(self):
        """The per-pair SecureBox key is static across process lives, so
        a captured high-version 'topics' announcement replays cleanly at
        the crypto layer; the incarnation binding must reject it
        (ADVICE r1: the replayed watermark wedged topic membership)."""
        routers = _mesh(2)
        a, b = routers
        try:
            b.alow("room", lambda m, pk: None)
            pump(routers)
            assert a.peers_on("room") == [b.public_key]

            # attacker replays a capture from b's PREVIOUS incarnation:
            # sealed under the same static pair key, huge version, empty
            # topic set, dead inst token
            from crdt_tpu.net.transport import SecureBox

            old_box = SecureBox(b._secret, bytes.fromhex(a.public_key))
            b_raw = bytes.fromhex(b.public_key)
            from crdt_tpu.net.udp_router import _pack_any

            payload = _pack_any(
                {"t": "topics", "v": 999, "inst": "deadbeefdeadbeef",
                 "topics": []}
            )
            body = b_raw + old_box.encrypt(payload, aad=b_raw)
            assert a._on_envelope(body, b.addr)
            # the replay neither cleared the topic set nor poisoned the
            # version watermark
            assert a.peers_on("room") == [b.public_key]

            # a genuine follow-up announcement (true inst, v below the
            # replayed 999) still applies
            b.alow("room2", lambda m, pk: None)
            pump(routers)
            assert a.peers_on("room2") == [b.public_key]
        finally:
            for r in routers:
                r.close()

    def test_replayed_old_hello_cannot_wedge_membership(self):
        """A replayed plaintext hello carrying a dead incarnation token
        (spoofed source = the peer's real address) must not poison
        peer.inst: inst changes are only adopted from a fresh-nonce
        pong, so the peer's genuine announcements keep applying."""
        routers = _mesh(2)
        a, b = routers
        try:
            b.alow("room", lambda m, pk: None)
            pump(routers)
            assert a.peers_on("room") == [b.public_key]
            true_inst = a._peers[b.public_key].inst

            # attacker replays b's old-incarnation hello; the source
            # address check can be beaten by spoofing, so deliver it
            # as if it came from b's recorded address
            from crdt_tpu.codec.lib0 import Encoder

            enc = Encoder()
            enc.write_any(
                {"pk": b.public_key, "ack": True, "inst": "deadinst"}
            )
            b_addr = a._peers[b.public_key].addr
            a._on_hello(enc.to_bytes(), b_addr)
            # the dead inst was NOT adopted; a challenge went out and
            # b's pong (fresh nonce, live inst) settles the question
            assert a._peers[b.public_key].inst == true_inst
            pump(routers)
            assert a._peers[b.public_key].inst == true_inst

            # membership keeps working end to end
            b.alow("room3", lambda m, pk: None)
            pump(routers)
            assert a.peers_on("room3") == [b.public_key]
            assert a.peers_on("room") == [b.public_key]
        finally:
            for r in routers:
                r.close()



class TestRendezvousDiscovery:
    """Hyperswarm-reduction discovery (crdt.js:315): routers find each
    other through a rendezvous node's topic introductions — no static
    peer lists among the members."""

    def test_three_routers_discover_via_bootstrap_only(self):
        boot = UdpRouter(rendezvous=True)
        members = [UdpRouter(bootstrap=[boot.addr]) for _ in range(3)]
        routers = [boot] + members
        try:
            reps = [
                Replica(r, topic="room", client_id=i + 1)
                for i, r in enumerate(members)
            ]
            # constructing the replica starts the router, which dials
            # ONLY the bootstrap; intros must build the full mesh
            pump(routers, timeout_s=20.0)
            for m in members:
                others = {x.public_key for x in members if x is not m}
                assert others <= set(m.peers), (
                    m.public_key, m.peers
                )
            reps[0].set("m", "k0", 0)
            reps[1].push("l", "v1")
            reps[2].set("m", "k2", 2)
            pump(routers, timeout_s=20.0)
            first = dict(reps[0].c)
            assert first == dict(reps[1].c) == dict(reps[2].c)
            assert first["m"] == {"k0": 0, "k2": 2}
        finally:
            for r in routers:
                r.close()

    def test_late_joiner_discovers_existing_swarm(self):
        boot = UdpRouter(rendezvous=True)
        a = UdpRouter(bootstrap=[boot.addr])
        b = UdpRouter(bootstrap=[boot.addr])
        routers = [boot, a, b]
        try:
            ra = Replica(a, topic="room", client_id=1)
            rb = Replica(b, topic="room", client_id=2)
            pump(routers, timeout_s=20.0)
            ra.set("m", "k", "early")
            pump(routers, timeout_s=20.0)
            late_r = UdpRouter(bootstrap=[boot.addr])
            routers.append(late_r)
            late = Replica(late_r, topic="room", client_id=3)
            pump(routers, timeout_s=20.0)
            assert late.c["m"] == {"k": "early"}
            assert rb.c == ra.c == late.c
        finally:
            for r in routers:
                r.close()

    def test_rendezvous_node_subscribes_nothing(self):
        """The bootstrap node introduces without joining any topic —
        pure rendezvous, like a DHT node storing announcements."""
        boot = UdpRouter(rendezvous=True)
        a = UdpRouter(bootstrap=[boot.addr])
        b = UdpRouter(bootstrap=[boot.addr])
        routers = [boot, a, b]
        try:
            ra = Replica(a, topic="room", client_id=1)
            rb = Replica(b, topic="room", client_id=2)
            pump(routers, timeout_s=20.0)
            assert boot._handlers == {}
            ra.set("m", "k", 1)
            pump(routers, timeout_s=20.0)
            assert rb.c["m"] == {"k": 1}
        finally:
            for r in routers:
                r.close()


_BOOT_CHILD = r"""
import sys, time
sys.path.insert(0, "@REPO@")
from crdt_tpu.net.replica import Replica
from crdt_tpu.net.udp_router import UdpRouter

boot_ip, boot_port, who = sys.argv[1], int(sys.argv[2]), sys.argv[3]
router = UdpRouter(bootstrap=[(boot_ip, boot_port)])
rep = Replica(router, topic="disco", client_id=int(who))
rep.set("m", f"from{who}", who)
# generous: three cold interpreters importing jax may serialize for
# tens of seconds before the fabric even forms
deadline = time.monotonic() + 90
while time.monotonic() < deadline:
    router.poll()
    m = rep.c.get("m", {})
    # wait until we hold ALL THREE writers' keys (discovered through
    # the bootstrap only) and our outbox is drained
    if len(m) >= 3 and not router.endpoint.pending:
        sys.exit(0)
    time.sleep(0.002)
sys.exit(3)
"""


class TestRendezvousCrossProcess:
    def test_three_processes_find_each_other_via_bootstrap(self, tmp_path):
        """VERDICT r2 item #7's acceptance shape: three OS processes,
        each knowing only the bootstrap address, converge."""
        repo = str(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        script = tmp_path / "member.py"
        script.write_text(_BOOT_CHILD.replace("@REPO@", repo))

        boot = UdpRouter(rendezvous=True)
        boot.start(None)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        children = [
            subprocess.Popen(
                [sys.executable, str(script), "127.0.0.1",
                 str(boot.endpoint.port), str(i + 1)],
                env=env,
            )
            for i in range(3)
        ]
        try:
            deadline = time.monotonic() + 120
            done = [None] * 3
            while time.monotonic() < deadline:
                boot.poll()
                for i, ch in enumerate(children):
                    if done[i] is None:
                        done[i] = ch.poll()
                if all(d is not None for d in done):
                    break
                time.sleep(0.005)
            assert done == [0, 0, 0], f"child exit codes: {done}"
        finally:
            for ch in children:
                if ch.poll() is None:
                    ch.kill()
            boot.close()


class TestRendezvousRobustness:
    def test_malformed_intro_entries_do_not_kill_poll(self):
        """Wrong-typed intro fields from an authenticated peer must be
        skipped, not crash the event loop."""
        routers = _mesh(2)
        a, b = routers
        try:
            bad = {"t": "intro", "peers": [
                {"pk": 5, "ip": "1.2.3.4", "port": 1},      # pk not str
                {"pk": "ab", "ip": 7, "port": 1},           # ip not str
                {"pk": "cd", "port": 1},                    # no ip
                "not-a-dict",
                {"pk": "ef" * 32, "ip": "host.invalid", "port": "x"},
            ]}
            # send through b's real box so a decrypts it as genuine
            peer_a = b._peers[a.public_key]
            b._send_envelope(peer_a, bad)
            pump(routers)  # must not raise
            assert a.endpoint.port  # loop alive
        finally:
            for r in routers:
                r.close()

    def test_dead_holder_ages_out_of_introductions(self):
        """A crashed member past the announce TTL is not handed to new
        joiners as a dial target."""
        boot = UdpRouter(rendezvous=True, announce_ttl=0.2)
        # the TTL rides the wire: aging uses the MEMBER's declared ttl
        a = UdpRouter(bootstrap=[boot.addr], announce_ttl=0.2)
        routers = [boot, a]
        try:
            Replica(a, topic="room", client_id=1)
            pump(routers, timeout_s=20.0)
            # a "crashes": stop polling it, let its announcement age out
            a_pk = a.public_key
            time.sleep(0.35)
            late = UdpRouter(bootstrap=[boot.addr])
            routers.append(late)
            Replica(late, topic="room", client_id=2)
            # pump only boot+late: a is dead and must NOT be introduced
            pump([boot, late], timeout_s=20.0)
            assert a_pk not in late.peers
        finally:
            for r in routers:
                r.close()

    @staticmethod
    def _poll_until(routers, cond, timeout_s=20.0):
        """Poll a router set until ``cond()`` — tolerant of exhausted
        retransmits (dials at a dead bootstrap are EXPECTED to burn
        out here, unlike pump(), which treats that as failure)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            for r in routers:
                r.poll()
            if cond():
                return
            time.sleep(0.005)
        raise TimeoutError("condition not reached")

    def test_newcomer_joins_via_second_bootstrap_after_first_dies(self):
        """VERDICT r3 item 6: multi-bootstrap failover. Every
        configured bootstrap is dialed; killing one mid-swarm leaves a
        newcomer joinable through the survivor, the dead dial costing
        only its own burned retransmits."""
        b1 = UdpRouter(rendezvous=True)
        b2 = UdpRouter(rendezvous=True)
        routers = [b1, b2]
        try:
            for b in (b1, b2):
                b.start(None)
                b.alow("room", lambda m, pk: None)
            boots = [b1.addr, b2.addr]
            a = UdpRouter(bootstrap=boots)
            routers.append(a)
            a.start(None)
            a.alow("room", lambda m, pk: None)
            self._poll_until(
                routers,
                lambda: b1.public_key in a._rendezvous_pks
                and b2.public_key in a._rendezvous_pks,
            )
            b1.close()  # kill one rendezvous node mid-swarm
            late = UdpRouter(bootstrap=boots)
            routers.append(late)
            late.start(None)
            late.alow("room", lambda m, pk: None)
            self._poll_until(
                [b2, a, late],
                lambda: a.public_key in late.peers
                and late.public_key in a.peers,
            )
            # introducer trust came from the SURVIVOR, proven not claimed
            assert b2.public_key in late._rendezvous_pks
        finally:
            for r in routers:
                r.close()

    def test_member_reannounces_to_restarted_rendezvous(self):
        """A rendezvous node that restarts (fresh process, same
        address/key) loses its member table; the member's TTL refresh
        plus the incarnation challenge re-register it, so a newcomer
        arriving AFTER the restart still gets introduced."""
        seed = bytes(range(32))
        boot = UdpRouter(rendezvous=True, seed=seed, announce_ttl=0.3)
        boot.start(None)
        port = boot.endpoint.port
        a = UdpRouter(bootstrap=[("127.0.0.1", port)], announce_ttl=0.3)
        routers = [boot, a]
        try:
            a.start(None)
            a.alow("room", lambda m, pk: None)
            boot.alow("room", lambda m, pk: None)
            self._poll_until(
                routers, lambda: boot.public_key in a._rendezvous_pks
            )
            boot.close()
            # restart: same identity and port, empty peer table
            boot2 = UdpRouter(rendezvous=True, seed=seed, port=port)
            routers[0] = boot2
            boot2.start(None)
            boot2.alow("room", lambda m, pk: None)
            # the member's refresh re-registers it at the new process
            self._poll_until(
                [boot2, a],
                lambda: a.public_key in boot2.peers
                and "room" in boot2._peers[a.public_key].topics,
                timeout_s=30.0,
            )
            late = UdpRouter(bootstrap=[("127.0.0.1", port)])
            routers.append(late)
            late.start(None)
            late.alow("room", lambda m, pk: None)
            self._poll_until(
                [boot2, a, late],
                lambda: a.public_key in late.peers
                and late.public_key in a.peers,
                timeout_s=30.0,
            )
        finally:
            for r in routers:
                r.close()

    def test_intro_from_non_bootstrap_peer_ignored(self):
        """Only peers reached at a configured bootstrap address may
        introduce: an ordinary member's intro must not make us dial."""
        boot = UdpRouter(rendezvous=True)
        a = UdpRouter(bootstrap=[boot.addr])
        b = UdpRouter(bootstrap=[boot.addr])
        routers = [boot, a, b]
        try:
            Replica(a, topic="room", client_id=1)
            Replica(b, topic="room", client_id=2)
            pump(routers, timeout_s=20.0)
            assert b.public_key in a.peers  # mesh formed via boot
            # b (an ordinary member) tries to introduce a to a fake peer
            peer_a = b._peers[a.public_key]
            b._send_envelope(peer_a, {"t": "intro", "peers": [
                {"pk": "ab" * 32, "ip": "127.0.0.1", "port": 1}
            ]})
            pump(routers, timeout_s=20.0)
            assert "ab" * 32 not in a.peers
            # while the same intro FROM the bootstrap would be honored
            assert boot.public_key in a._rendezvous_pks
            assert b.public_key not in a._rendezvous_pks
        finally:
            for r in routers:
                r.close()

    def test_spoofed_bootstrap_hello_does_not_mint_trust(self):
        """A plaintext hello claiming a bootstrap source address must
        not grant introducer trust: only a nonce-proven pong FROM the
        bootstrap address does."""
        boot = UdpRouter(rendezvous=True)
        victim = UdpRouter(bootstrap=[boot.addr])
        attacker = UdpRouter()
        routers = [boot, victim, attacker]
        try:
            Replica(victim, topic="room", client_id=1)
            pump(routers, timeout_s=20.0)
            assert boot.public_key in victim._rendezvous_pks
            # attacker completes an ordinary key exchange with victim
            attacker.add_peer(*victim.addr)
            pump(routers, timeout_s=20.0)
            assert attacker.public_key in victim.peers
            # forge a hello whose claimed source is the bootstrap addr
            # (simulate source spoofing by calling the handler with the
            # bootstrap address directly)
            from crdt_tpu.net.udp_router import _pack_any

            body = _pack_any({
                "pk": attacker.public_key, "ack": True,
                "inst": attacker._inst,
            })
            victim._on_hello(body, boot.addr)
            # trust NOT granted from the unauthenticated claim...
            assert attacker.public_key not in victim._rendezvous_pks
            # ...and the attacker's authenticated intro is ignored
            peer_v = attacker._peers[victim.public_key]
            attacker._send_envelope(peer_v, {"t": "intro", "peers": [
                {"pk": "cd" * 32, "ip": "127.0.0.1", "port": 9}
            ]})
            pump(routers, timeout_s=20.0)
            assert "cd" * 32 not in victim.peers
        finally:
            for r in routers:
                r.close()


class TestIntroductionPunch:
    """The cone-NAT traversal mechanics (udp_router module docstring):
    hole punching IS (a) observed-address introductions, (b) BOTH
    sides dialing out on one introduction, (c) hellos that retransmit
    through the window where the other side's mapping does not exist
    yet. A real NAT cannot be interposed on loopback sockets, so each
    property is pinned directly."""

    def test_intro_makes_both_sides_dial_observed_addresses(self):
        boot = UdpRouter(rendezvous=True)
        a = UdpRouter(bootstrap=[boot.addr])
        routers = [boot, a]
        try:
            ra = Replica(a, topic="room", client_id=1)
            pump(routers, timeout_s=20.0)

            dials: dict = {"a": [], "b": []}
            orig_a = a._send_hello
            a._send_hello = lambda ip, port, **kw: (
                dials["a"].append((ip, port)), orig_a(ip, port, **kw)
            )[-1]
            b = UdpRouter(bootstrap=[boot.addr])
            routers.append(b)
            orig_b = b._send_hello
            b._send_hello = lambda ip, port, **kw: (
                dials["b"].append((ip, port)), orig_b(ip, port, **kw)
            )[-1]
            rb = Replica(b, topic="room", client_id=2)
            pump(routers, timeout_s=20.0)

            # (a)+(b): the EXISTING member dialed the newcomer's
            # observed transport address, and the newcomer dialed the
            # existing member's — one introduction, two outbound
            # opens, which is the punch
            assert b.addr in dials["a"], (dials, b.addr)
            assert a.addr in dials["b"], (dials, a.addr)
            assert a.public_key in b.peers and b.public_key in a.peers
            rb.set("m", "k", 1)
            pump(routers, timeout_s=20.0)
            assert dict(ra.c) == dict(rb.c)
        finally:
            for r in routers:
                r.close()

    def test_hello_survives_unopened_window(self):
        """The race half of the punch: A dials an address whose owner
        is not processing packets yet (the NAT-mapping-not-open
        window); once the owner starts polling, the retransmitting
        hello completes the link with no new dial from A."""
        a = UdpRouter()
        b = UdpRouter()
        try:
            a.start()
            b.start()
            a.add_peer(*b.addr)  # ONE dial, before b ever polls
            deadline = time.monotonic() + 1.0
            while time.monotonic() < deadline:
                a.poll()  # only A pumps: hello keeps retransmitting
                time.sleep(0.002)
            assert b.public_key not in a.peers  # window still closed
            pump([a, b], timeout_s=20.0)  # b joins the loop
            assert b.public_key in a.peers
            assert a.public_key in b.peers
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# symmetric-NAT traversal: port prediction + relay fallback
# ---------------------------------------------------------------------------

from crdt_tpu.net.faults import (  # noqa: E402
    ConeNat,
    NatFabric,
    SymmetricNat,
    install_nat,
    pump_until,
)


def _nat_pair(nat_a, nat_b, **router_kw):
    """Rendezvous + two members behind simulated NATs on one virtual
    fabric. Returns (routers, a, b)."""
    fabric = NatFabric()
    boot = UdpRouter(rendezvous=True)
    install_nat(boot, fabric)
    router_kw.setdefault("dial_retry_s", 0.05)
    a = UdpRouter(bootstrap=[boot.addr], **router_kw)
    install_nat(a, fabric, nat_a)
    b = UdpRouter(bootstrap=[boot.addr], **router_kw)
    install_nat(b, fabric, nat_b)
    return [boot, a, b], a, b


class TestSymmetricNatTraversal:
    """A symmetric NAT allocates a NEW external port per destination,
    so the address the rendezvous observed is a dead letter to every
    introduced stranger — the introduction alone (TestIntroductionPunch)
    can no longer traverse. Sequential allocation makes the live
    mapping predictable, and the dial scheduler's probe spray finds
    it."""

    def test_introduction_alone_is_filtered(self):
        """Ground truth for the scenario: with retries/prediction OFF,
        the introduced members stay strangers — their dials at the
        observed addresses die at each other's NAT filters."""
        routers, a, b = _nat_pair(
            SymmetricNat(21000), SymmetricNat(23000),
            port_prediction=False, relay_after_s=3600.0,
            dial_retry_s=3600.0,
        )
        try:
            ra = Replica(a, topic="room", client_id=1)
            rb = Replica(b, topic="room", client_id=2)
            del ra, rb
            deadline = time.monotonic() + 1.5
            while time.monotonic() < deadline:
                for r in routers:
                    r.poll()
                time.sleep(0.002)
            assert b.public_key not in a.peers
            assert a.public_key not in b.peers
            # the dials really happened and really were filtered
            assert a.endpoint.stats["filtered"] > 0
            assert b.endpoint.stats["filtered"] > 0
        finally:
            for r in routers:
                r.close()

    def test_converges_via_port_prediction(self):
        """Symmetric vs (port-restricted) cone: the cone side's probe
        at observed+1 lands in the symmetric side's sequentially
        allocated mapping, each side's spray opens its own filter, and
        the ordinary hello/challenge handshake completes a DIRECT
        path. Replicas then converge over it."""
        from crdt_tpu.utils.trace import Tracer, set_tracer

        tracer = set_tracer(Tracer(enabled=True))
        routers, a, b = _nat_pair(
            SymmetricNat(21000), ConeNat(22000),
            predict_after=1, relay_after_s=3600.0,  # no relay: punch or bust
        )
        try:
            ra = Replica(a, topic="room", client_id=1)
            rb = Replica(b, topic="room", client_id=2)
            pump_until(
                routers,
                lambda: (
                    b.public_key in a._peers and a._peers[b.public_key].direct
                    and a.public_key in b._peers
                    and b._peers[a.public_key].direct
                ),
                timeout_s=30.0,
            )
            assert a.stats["predict_probes"] > 0
            assert tracer.counters("router.")["router.dial_retries"] > 0
            ra.set("m", "ka", 1)
            rb.set("m", "kb", 2)
            pump_until(
                routers,
                lambda: dict(ra.c) == dict(rb.c)
                and ra.c.get("m", {}).get("kb") == 2,
                timeout_s=30.0,
            )
            # the punched mapping, not the advertised one, carries it:
            # b appears to a at its NAT address
            assert a._peers[b.public_key].addr[1] >= 22000
        finally:
            set_tracer(Tracer(enabled=False))
            for r in routers:
                r.close()


class TestRelayFallback:
    """Symmetric vs symmetric with sequentially interleaved probes
    never self-punches; the dial deadline falls back to forwarding
    end-to-end sealed frames through the introducer."""

    def test_converges_via_relay_with_prediction_disabled(self):
        from crdt_tpu.utils.trace import Tracer, set_tracer

        tracer = set_tracer(Tracer(enabled=True))
        routers, a, b = _nat_pair(
            SymmetricNat(21000), SymmetricNat(23000),
            port_prediction=False, relay_after_s=0.3,
        )
        boot = routers[0]
        try:
            ra = Replica(a, topic="room", client_id=1,
                         probe_retry_s=0.1, anti_entropy_s=0.2)
            rb = Replica(b, topic="room", client_id=2,
                         probe_retry_s=0.1, anti_entropy_s=0.2)
            ra.set("m", "ka", 1)
            rb.set("m", "kb", 2)
            pump_until(
                routers,
                lambda: dict(ra.c) == dict(rb.c)
                and ra.c.get("m", {}).get("kb") == 2
                and ra.c.get("m", {}).get("ka") == 1,
                timeout_s=30.0,
            )
            # converged WITHOUT a direct path, through the rendezvous
            pa = a._peers[b.public_key]
            assert not pa.direct and pa.relay == boot.public_key
            assert boot.stats["relay_frames_forwarded"] > 0
            assert boot.stats["relay_bytes_forwarded"] > 0
            assert a.stats["relay_sends"] > 0
            counters = tracer.counters("router.relay")
            assert counters["router.relay_frames_forwarded"] > 0
            assert counters["router.relay_elections"] > 0
        finally:
            set_tracer(Tracer(enabled=False))
            for r in routers:
                r.close()

    def test_later_probe_success_upgrades_relay_to_direct(self):
        """The relay is a bridge, not a destination: once prediction
        is allowed to run and a probe lands, the proven direct path
        replaces the relay leg in place."""
        routers, a, b = _nat_pair(
            SymmetricNat(21000), ConeNat(22000),
            port_prediction=False, relay_after_s=0.2, predict_after=1,
        )
        try:
            ra = Replica(a, topic="room", client_id=1)
            rb = Replica(b, topic="room", client_id=2)
            ra.set("m", "early", 7)
            pump_until(
                routers,
                lambda: rb.c.get("m", {}).get("early") == 7,
                timeout_s=30.0,
            )
            assert not a._peers[b.public_key].direct  # relayed so far
            a._port_prediction = True
            b._port_prediction = True
            pump_until(
                routers,
                lambda: a._peers[b.public_key].direct
                and b._peers[a.public_key].direct,
                timeout_s=30.0,
            )
            assert a._peers[b.public_key].relay is None
            assert a.stats["relay_upgrades"] + b.stats["relay_upgrades"] > 0
            ra.set("m", "late", 8)  # post-upgrade traffic rides direct
            pump_until(
                routers,
                lambda: rb.c.get("m", {}).get("late") == 8,
                timeout_s=15.0,
            )
        finally:
            for r in routers:
                r.close()

    def test_fresh_intro_reopens_expired_dial_for_relayed_peer(self):
        """A relay-routed pair whose dial window expired must not be
        stuck relayed forever: a later introduction carries a fresh
        observed address, re-opens the dial, and the prediction
        escalation upgrades the pair to direct."""
        routers, a, b = _nat_pair(
            SymmetricNat(21000), ConeNat(22000),
            port_prediction=False, relay_after_s=0.2, predict_after=1,
            dial_give_up_s=0.5,
        )
        boot = routers[0]
        try:
            ra = Replica(a, topic="room", client_id=1)
            rb = Replica(b, topic="room", client_id=2)
            ra.set("m", "x", 1)
            pump_until(
                routers,
                lambda: rb.c.get("m", {}).get("x") == 1,
                timeout_s=30.0,
            )
            end = time.monotonic() + 0.8  # let the 0.5s dials expire
            while time.monotonic() < end:
                for r in routers:
                    r.poll()
                time.sleep(0.002)
            assert not a._dials and not b._dials
            assert not a._peers[b.public_key].direct  # still relayed
            a._port_prediction = True
            b._port_prediction = True
            bs = boot._peers
            for src, dst in ((a, b), (b, a)):
                src._apply_intro(
                    {"peers": [{
                        "pk": dst.public_key,
                        "ip": bs[dst.public_key].addr[0],
                        "port": bs[dst.public_key].addr[1],
                    }]},
                    introducer=boot.public_key,
                )
            assert b.public_key in a._dials  # dial re-opened
            pump_until(
                routers,
                lambda: a._peers[b.public_key].direct
                and b._peers[a.public_key].direct,
                timeout_s=30.0,
            )
            assert a._peers[b.public_key].relay is None
        finally:
            for r in routers:
                r.close()

    def test_dead_relay_triggers_reelection_not_a_wedge(self):
        fabric = NatFabric()
        b1 = UdpRouter(rendezvous=True)
        install_nat(b1, fabric)
        b2 = UdpRouter(rendezvous=True)
        install_nat(b2, fabric)
        boots = [b1.addr, b2.addr]
        kw = dict(bootstrap=boots, dial_retry_s=0.05,
                  port_prediction=False, relay_after_s=0.2,
                  relay_stale_s=0.4)
        a = UdpRouter(**kw)
        install_nat(a, fabric, SymmetricNat(31000))
        b = UdpRouter(**kw)
        install_nat(b, fabric, SymmetricNat(33000))
        routers = [b1, b2, a, b]
        try:
            ra = Replica(a, topic="room", client_id=1,
                         probe_retry_s=0.1, anti_entropy_s=0.2)
            rb = Replica(b, topic="room", client_id=2,
                         probe_retry_s=0.1, anti_entropy_s=0.2)
            ra.set("m", "pre", 1)
            pump_until(
                routers,
                lambda: rb.c.get("m", {}).get("pre") == 1,
                timeout_s=30.0,
            )
            relay0 = a._peers[b.public_key].relay
            dead = b1 if relay0 == b1.public_key else b2
            survivor = b2 if dead is b1 else b1
            elections0 = a.stats["relay_elections"]
            dead.close()
            live = [r for r in routers if r is not dead]
            ra.set("m", "after-death", 42)
            pump_until(
                live,
                lambda: rb.c.get("m", {}).get("after-death") == 42,
                timeout_s=40.0,
            )
            assert a.stats["relay_elections"] > elections0
            assert a._peers[b.public_key].relay == survivor.public_key
        finally:
            for r in routers:
                r.close()  # idempotent: the dead relay closed earlier

    def test_saturated_relay_sheds_and_recovers(self):
        """Per-source byte budgets: a relay over budget NAKs, the
        sender pauses its relay leg (sheds to its own retry cadence),
        and the refill lets the pair converge anyway."""
        fabric = NatFabric()
        # budget below ONE side's handshake+sync footprint: the bucket
        # must bind during the initial exchange, whatever the timing
        boot = UdpRouter(rendezvous=True, relay_budget_bytes=400,
                         relay_refill_bps=1500)
        install_nat(boot, fabric)
        kw = dict(bootstrap=[boot.addr], dial_retry_s=0.05,
                  port_prediction=False, relay_after_s=0.2)
        a = UdpRouter(**kw)
        install_nat(a, fabric, SymmetricNat(21000))
        b = UdpRouter(**kw)
        install_nat(b, fabric, SymmetricNat(23000))
        routers = [boot, a, b]
        try:
            ra = Replica(a, topic="room", client_id=1,
                         probe_retry_s=0.1, anti_entropy_s=0.15)
            rb = Replica(b, topic="room", client_id=2,
                         probe_retry_s=0.1, anti_entropy_s=0.15)
            for i in range(8):
                (ra if i % 2 else rb).set("m", f"k{i}", i)
            pump_until(
                routers,
                lambda: dict(ra.c) == dict(rb.c)
                and len(ra.c.get("m", {})) == 8,
                timeout_s=40.0,
            )
            assert boot.stats["relay_sheds"] > 0  # budget really bound
        finally:
            for r in routers:
                r.close()
