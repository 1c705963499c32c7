"""Control-plane KV store: rank-0 hosts a TCP server, every rank connects a client.

Reference: paddle/phi/core/distributed/store/tcp_store.cc (MasterDaemon command
loop) and store.py (Store python surface). TPU-native twist: the server is a
native C++ .so (tcp_store.cc, built on demand with g++) so it stays responsive
while the trainer holds the GIL inside a compiled step; a pure-Python threaded
server is the fallback when no compiler is available. Client and fallback speak
the same length-prefixed wire protocol documented in tcp_store.cc.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import socket
import socketserver
import struct
import subprocess
import threading
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tcp_store.cc")

_CMD_SET, _CMD_GET, _CMD_ADD, _CMD_WAIT, _CMD_DEL, _CMD_NUM, _CMD_CLR = 1, 2, 3, 4, 5, 6, 7


def _build_native():
    """Compile tcp_store.cc to a shared library next to it, cached under the
    source's content hash (utils/cpp_extension.load's key): a copied tree
    rewrites mtimes, so "the .so is newer" proves nothing about which source
    built it."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(os.path.dirname(_SRC), f"libtcp_store_{digest}.so")
    if not os.path.exists(so_path):
        # build aside and rename: workers starting together must never load
        # a half-written library
        tmp = f"{so_path}.{os.getpid()}.tmp"
        cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread", _SRC, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, so_path)
    return so_path


_native_lib = None
_native_failed = False


def _native():
    global _native_lib, _native_failed
    if _native_lib is None and not _native_failed:
        try:
            lib = ctypes.CDLL(_build_native())
            lib.tps_start.restype = ctypes.c_void_p
            lib.tps_start.argtypes = [ctypes.c_int]
            lib.tps_port.restype = ctypes.c_int
            lib.tps_port.argtypes = [ctypes.c_void_p]
            lib.tps_stop.argtypes = [ctypes.c_void_p]
            _native_lib = lib
        except Exception:
            _native_failed = True
    return _native_lib


# ------------------------------------------------------------------ fallback server
class _PyHandler(socketserver.BaseRequestHandler):
    def _read(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self.request.recv(n - len(buf))
            if not chunk:
                raise ConnectionError
            buf += chunk
        return buf

    def _read_lv(self):
        (n,) = struct.unpack("<I", self._read(4))
        return self._read(n) if n else b""

    def handle(self):
        srv = self.server
        try:
            while True:
                cmd = self._read(1)[0]
                if cmd == _CMD_SET:
                    key, val = self._read_lv(), self._read_lv()
                    with srv.cond:
                        srv.data[key] = val
                        srv.cond.notify_all()
                    self.request.sendall(b"\x01")
                elif cmd == _CMD_GET:
                    key = self._read_lv()
                    with srv.cond:
                        val = srv.data.get(key)
                    if val is None:
                        self.request.sendall(b"\x00")
                    else:
                        self.request.sendall(b"\x01" + struct.pack("<I", len(val)) + val)
                elif cmd == _CMD_ADD:
                    key = self._read_lv()
                    (delta,) = struct.unpack("<q", self._read(8))
                    with srv.cond:
                        prev = srv.data.get(key)
                        # non-8-byte values count as 0, matching the native server
                        cur = struct.unpack("<q", prev)[0] if prev is not None and len(prev) == 8 else 0
                        new = cur + delta
                        srv.data[key] = struct.pack("<q", new)
                        srv.cond.notify_all()
                    self.request.sendall(struct.pack("<q", new))
                elif cmd == _CMD_WAIT:
                    key = self._read_lv()
                    (timeout_ms,) = struct.unpack("<I", self._read(4))
                    deadline = None if timeout_ms == 0 else time.monotonic() + timeout_ms / 1e3
                    with srv.cond:
                        while key not in srv.data:
                            remaining = None if deadline is None else deadline - time.monotonic()
                            if remaining is not None and remaining <= 0:
                                break
                            srv.cond.wait(remaining)
                        found = key in srv.data
                    self.request.sendall(b"\x01" if found else b"\x00")
                elif cmd == _CMD_DEL:
                    key = self._read_lv()
                    with srv.cond:
                        existed = srv.data.pop(key, None) is not None
                    self.request.sendall(b"\x01" if existed else b"\x00")
                elif cmd == _CMD_NUM:
                    with srv.cond:
                        n = len(srv.data)
                    self.request.sendall(struct.pack("<I", n))
                elif cmd == _CMD_CLR:
                    prefix = self._read_lv()
                    with srv.cond:
                        doomed = [k for k in srv.data if k.startswith(prefix)]
                        for k in doomed:
                            del srv.data[k]
                    self.request.sendall(struct.pack("<I", len(doomed)))
                else:
                    return
        except (ConnectionError, OSError):
            return


class _PyServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, port):
        super().__init__(("0.0.0.0", port), _PyHandler)
        self.data = {}
        self.cond = threading.Condition()


class StoreServer:
    """Hosts the KV store. Prefers the native C++ server; falls back to Python."""

    def __init__(self, port=0, prefer_native=True):
        self._handle = None
        self._py = None
        lib = _native() if prefer_native else None
        if lib is not None:
            self._handle = lib.tps_start(port)
        if self._handle:
            self.port = lib.tps_port(self._handle)
            self.native = True
        else:
            self._py = _PyServer(port)
            self.port = self._py.server_address[1]
            self.native = False
            t = threading.Thread(target=self._py.serve_forever, daemon=True)
            t.start()

    def stop(self):
        if self._handle:
            _native().tps_stop(self._handle)
            self._handle = None
        if self._py:
            self._py.shutdown()
            self._py.server_close()
            self._py = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


# ------------------------------------------------------------------ client
class TCPStore:
    """Reference: python/paddle/distributed `core.TCPStore` surface.

    ``TCPStore(host, port, world_size, is_master)``: the master also spins up the
    server (native if possible). All methods are blocking RPCs.
    """

    def __init__(self, host="127.0.0.1", port=0, world_size=1, is_master=False,
                 timeout=120.0, prefer_native=True):
        self.server = None
        if is_master:
            self.server = StoreServer(port, prefer_native=prefer_native)
            port = self.server.port
        self.host, self.port, self.world_size = host, port, world_size
        self._sock = None
        self._lock = threading.Lock()
        self._timeout = timeout
        self._connect(timeout)

    def _connect(self, timeout):
        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                s = socket.create_connection((self.host, self.port), timeout=5)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.settimeout(None)
                self._sock = s
                return
            except OSError as e:
                last = e
                time.sleep(0.1)
        raise TimeoutError(f"could not reach store at {self.host}:{self.port}: {last}")

    def _read(self, n):
        buf = b""
        while len(buf) < n:
            chunk = self._sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("store server closed connection")
            buf += chunk
        return buf

    @staticmethod
    def _lv(b):
        return struct.pack("<I", len(b)) + b

    @staticmethod
    def _enc(v):
        if isinstance(v, bytes):
            return v
        if isinstance(v, str):
            return v.encode()
        return bytes(v)

    def set(self, key, value):
        k, v = self._enc(key), self._enc(value)
        with self._lock:
            self._sock.sendall(bytes([_CMD_SET]) + self._lv(k) + self._lv(v))
            assert self._read(1) == b"\x01"

    def _get_once(self, key):
        k = self._enc(key)
        with self._lock:
            self._sock.sendall(bytes([_CMD_GET]) + self._lv(k))
            if self._read(1) == b"\x00":
                return None
            (n,) = struct.unpack("<I", self._read(4))
            return self._read(n) if n else b""

    def get(self, key, wait=True, timeout=None):
        """Blocking get (paddle semantics: get waits for the key). WAIT and GET
        are separate RPCs, so a concurrent delete can sneak between them — loop
        until the value is actually in hand or the deadline passes."""
        if not wait:
            return self._get_once(key)
        t = timeout if timeout is not None else self._timeout
        deadline = time.monotonic() + t
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not self.wait_key(key, remaining):
                raise TimeoutError(f"store key {key!r} never appeared")
            val = self._get_once(key)
            if val is not None:
                return val

    def add(self, key, delta=1):
        k = self._enc(key)
        with self._lock:
            self._sock.sendall(bytes([_CMD_ADD]) + self._lv(k) + struct.pack("<q", delta))
            return struct.unpack("<q", self._read(8))[0]

    def wait_key(self, key, timeout=0.0):
        """Block until key exists. timeout<=0 waits forever. Returns found."""
        k = self._enc(key)
        ms = max(0, int(timeout * 1000))
        with self._lock:
            self._sock.sendall(bytes([_CMD_WAIT]) + self._lv(k) + struct.pack("<I", ms))
            return self._read(1) == b"\x01"

    def wait(self, keys, timeout=None):
        t = timeout if timeout is not None else self._timeout
        for key in keys if isinstance(keys, (list, tuple)) else [keys]:
            if not self.wait_key(key, t):
                raise TimeoutError(f"store key {key!r} never appeared")

    def delete_key(self, key):
        k = self._enc(key)
        with self._lock:
            self._sock.sendall(bytes([_CMD_DEL]) + self._lv(k))
            return self._read(1) == b"\x01"

    def num_keys(self):
        with self._lock:
            self._sock.sendall(bytes([_CMD_NUM]))
            return struct.unpack("<I", self._read(4))[0]

    def clear(self, prefix=""):
        """Delete every key starting with `prefix` ("" = all). Returns count."""
        p = self._enc(prefix)
        with self._lock:
            self._sock.sendall(bytes([_CMD_CLR]) + self._lv(p))
            return struct.unpack("<I", self._read(4))[0]

    def barrier(self, name, world_size=None, timeout=None):
        """All `world_size` participants block until everyone arrives."""
        n = world_size or self.world_size
        t = timeout if timeout is not None else self._timeout
        arrived = self.add(f"__barrier/{name}/count", 1)
        if arrived >= n:
            self.set(f"__barrier/{name}/done", b"1")
        if not self.wait_key(f"__barrier/{name}/done", t):
            raise TimeoutError(f"barrier {name!r}: {arrived}/{n} after {t}s")

    def close(self):
        if self._sock:
            self._sock.close()
            self._sock = None
        if self.server:
            self.server.stop()
            self.server = None
