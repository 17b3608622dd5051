"""Parcelport security: auth handshake, bind policy, stale-.so guard,
and backend gating — regression tests for findings of the round-2/3
reviews.

The core property under test: bytes from an unauthenticated connection
must NEVER reach pickle. A raw TCP client sends a pickled payload whose
deserialization would have an observable side effect; with a secret
configured it must be dropped, while a client that completes the HMAC
handshake (dist/auth.py) bootstraps normally.
"""

import os
import pickle
import socket
import struct
import threading
import time

import pytest

from hpx_tpu.dist import auth

SECRET = "test-secret-1234"


class TestAuthFrames:
    def test_roundtrip(self):
        nonce = os.urandom(auth.NONCE_LEN)
        assert auth.parse(auth.hello_frame(nonce)) == (auth.T_HELLO,
                                                       nonce)
        m = auth.mac(SECRET, nonce, b"srv")
        t, got_m, got_n = auth.parse(auth.reply_frame(m, nonce))
        assert (t, got_m, got_n) == (auth.T_REPLY, m, nonce)
        assert auth.parse(auth.final_frame(m)) == (auth.T_FINAL, m)

    @pytest.mark.parametrize("junk", [
        b"", b"HPX", b"HPXA", b"HPXA\x07" + b"x" * 16,
        b"HPXA\x01short", b"HPXA\x02" + b"x" * 10,
        b"\x80\x04pickle-looking-bytes", b"HPXB\x01" + b"x" * 16,
    ])
    def test_malformed_dropped(self, junk):
        assert auth.parse(junk) is None

    def test_wrong_secret_fails_verify(self):
        nonce = os.urandom(auth.NONCE_LEN)
        m = auth.mac("other-secret", nonce, b"srv")
        assert not auth.verify(m, SECRET, nonce, b"srv")
        assert auth.verify(auth.mac(SECRET, nonce, b"srv"),
                           SECRET, nonce, b"srv")

    def test_role_separation(self):
        """A reflected srv proof must not pass as a cli proof."""
        nonce = os.urandom(auth.NONCE_LEN)
        assert not auth.verify(auth.mac(SECRET, nonce, b"srv"),
                               SECRET, nonce, b"cli")


class TestStaleSoGuard:
    def test_missing_symbol_raises(self):
        from hpx_tpu.native.loader import _bind_net

        class FakeLib:           # no hpxrt_net_* symbols at all
            pass

        with pytest.raises(RuntimeError, match="stale"):
            _bind_net(FakeLib())


class TestBackendGates:
    """Mosaic-only kernels must not be dispatched on a GPU backend
    (advisor r2: `not in ('cpu',)` misrouted rocm/cuda into pallas)."""

    def test_stencil_gpu_takes_xla_path(self, monkeypatch):
        import jax
        import jax.numpy as jnp
        from hpx_tpu.ops import stencil
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        u = jnp.arange(256, dtype=jnp.float32)
        got = stencil.heat_step_best(u, jnp.float32(0.25))
        want = stencil.heat_step(u, jnp.float32(0.25))
        import numpy as np
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
        got2 = stencil.multistep(u, jnp.float32(0.25), 3)
        want2 = stencil.xla_multistep(u, jnp.float32(0.25), 3)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                                   rtol=1e-6)

    def test_flash_gpu_interprets(self, monkeypatch):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import hpx_tpu.ops.attention_pallas as ap
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.standard_normal((1, 16, 2, 16),
                                                   np.float32))
                   for _ in range(3))
        out = ap.flash_attention(q, k, v, True, block_q=8, block_k=8)
        assert out.shape == q.shape    # interpret path, no Mosaic crash


class TestMultiNodePolicy:
    def test_multinode_without_secret_raises(self):
        from hpx_tpu.core.config import Configuration
        from hpx_tpu.core.errors import HpxError
        from hpx_tpu.dist.runtime import Runtime
        cfg = Configuration(overrides={
            "hpx.localities": "2", "hpx.locality": "0",
            "hpx.parcel.address": "203.0.113.7",   # not loopback
            "hpx.parcel.port": "0",
        })
        with pytest.raises(HpxError, match="secret"):
            Runtime(cfg)

    def test_multinode_allow_insecure_optout(self):
        """The explicit opt-out must get PAST the secret check (it then
        fails later trying to bind the non-local address — proving the
        policy gate, not the transport, was the decision point)."""
        from hpx_tpu.core.config import Configuration
        from hpx_tpu.dist.runtime import Runtime
        cfg = Configuration(overrides={
            "hpx.localities": "2", "hpx.locality": "0",
            "hpx.parcel.address": "203.0.113.7",
            "hpx.parcel.port": "0",
            "hpx.parcel.allow_insecure": "1",
        })
        with pytest.raises(OSError, match="203.0.113.7"):
            Runtime(cfg)


def _frame(payload: bytes) -> bytes:
    return struct.pack("<I", len(payload)) + payload


def _read_frame(sock: socket.socket, timeout: float = 10.0) -> bytes:
    sock.settimeout(timeout)
    hdr = b""
    while len(hdr) < 4:
        chunk = sock.recv(4 - len(hdr))
        if not chunk:
            raise EOFError
        hdr += chunk
    (n,) = struct.unpack("<I", hdr)
    body = b""
    while len(body) < n:
        chunk = sock.recv(n - len(body))
        if not chunk:
            raise EOFError
        body += chunk
    return body


class _Bomb:
    """Pickled payload with an observable deserialization side effect."""

    def __init__(self, path):
        self.path = path

    def __reduce__(self):
        return (open, (self.path, "w"))


class TestHandshakeEndToEnd:
    """Console runtime with a secret; a raw TCP client plays attacker
    then legitimate worker against the REAL endpoint + runtime."""

    @pytest.fixture()
    def console(self, tmp_path):
        from hpx_tpu.core.config import Configuration
        from hpx_tpu.dist.runtime import Runtime
        port = _free_port()
        cfg = Configuration(overrides={
            "hpx.localities": "2", "hpx.locality": "0",
            "hpx.parcel.address": "127.0.0.1",
            "hpx.parcel.port": str(port),
            "hpx.parcel.secret": SECRET,
            "hpx.startup_timeout": "20",
        })
        holder = {}

        def boot():
            holder["rt"] = Runtime(cfg)

        t = threading.Thread(target=boot, daemon=True)
        t.start()
        # wait for the listener
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                s = socket.create_connection(("127.0.0.1", port), 0.2)
                s.close()
                break
            except OSError:
                time.sleep(0.05)
        yield port, holder, t
        rt = holder.get("rt")
        if rt is not None:
            rt._stopped = True
            rt._endpoint.close()

    def test_unauth_pickle_dropped_then_handshake_boots(
            self, console, tmp_path):
        from hpx_tpu.dist.plugins import decode_payload, encode_payload
        from hpx_tpu.dist.serialization import deserialize, serialize

        def wire(msg):           # what _send_raw puts on the socket
            return encode_payload(serialize(msg), None)

        port, holder, boot_thread = console
        bomb_path = str(tmp_path / "pwned")

        # --- attacker: raw pickled parcel, no handshake ---------------
        atk = socket.create_connection(("127.0.0.1", port), 5)
        atk.sendall(_frame(b"\x00" + pickle.dumps(_Bomb(bomb_path))))
        # also a malformed auth frame for good measure
        atk.sendall(_frame(b"HPXA\x01short"))
        time.sleep(0.7)
        assert not os.path.exists(bomb_path), \
            "unauthenticated pickle was deserialized"
        assert holder.get("rt") is None, "bootstrap should still wait"
        atk.close()

        # --- wrong secret: REPLY comes, our FINAL check fails ---------
        bad = socket.create_connection(("127.0.0.1", port), 5)
        nonce = os.urandom(auth.NONCE_LEN)
        bad.sendall(_frame(auth.hello_frame(nonce)))
        body = _read_frame(bad)
        t, mac_srv, nonce_srv = auth.parse(body)
        assert t == auth.T_REPLY
        assert not auth.verify(mac_srv, "wrong-secret", nonce, b"srv")
        # (a real client would abort here; the server has not authed us:
        # a pickled hello must still be ignored)
        bad.sendall(_frame(wire(("hello", 1, "127.0.0.1", 1))))
        time.sleep(0.5)
        assert holder.get("rt") is None
        bad.close()

        # --- correct handshake, then HELLO -> TABLE -------------------
        cli = socket.create_connection(("127.0.0.1", port), 5)
        nonce = os.urandom(auth.NONCE_LEN)
        cli.sendall(_frame(auth.hello_frame(nonce)))
        t, mac_srv, nonce_srv = auth.parse(_read_frame(cli))
        assert t == auth.T_REPLY
        assert auth.verify(mac_srv, SECRET, nonce, b"srv")
        cli.sendall(_frame(auth.final_frame(
            auth.mac(SECRET, nonce_srv, b"cli"))))
        my_port = _free_port()
        cli.sendall(_frame(wire(("hello", 1, "127.0.0.1", my_port))))
        table = deserialize(decode_payload(_read_frame(cli)))
        assert table[0] == "table"
        assert set(table[1]) == {0, 1}
        boot_thread.join(10)
        assert holder.get("rt") is not None, "console failed to boot"
        cli.close()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p
