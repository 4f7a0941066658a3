"""``python server.py`` as a child: the system under test, through its CLI.

The parent never imports JAX: a chip belongs to one process at a time, and
the server child needs it. Booting copies ``chip_smoke.py``'s way: a free
port, the log in a file, ``listening on`` as the sign that warm-up is done,
SIGTERM and the clean drain at the end.
"""

from __future__ import annotations

import json
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

from .manifest import ROOT

BOOT_LIMIT_S = 1100.0  # a first run compiles; the driver allows it 1200 s


class ServerChild:
    def __init__(self, flags: list[str], log_path: Path, env: dict | None = None):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.log_path = log_path
        log_path.parent.mkdir(parents=True, exist_ok=True)
        self.cmd = [sys.executable, str(ROOT / "server.py"), "--host", "127.0.0.1",
                    "--port", str(self.port), *flags]
        self.t_start = time.monotonic()
        with open(log_path, "wb") as log:
            self.proc = subprocess.Popen(self.cmd, cwd=ROOT, env=env, stdout=log,
                                         stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 3000) -> str:
        return self.log_path.read_text(errors="replace")[-n:]

    def wait_listening(self) -> float:
        """Seconds from the child's start to ``listening on``."""
        while b"listening on" not in self.log_path.read_bytes():
            if self.proc.poll() is not None:
                raise RuntimeError(f"server exited with {self.proc.returncode} before listening; "
                                   f"log tail:\n{self.log_tail()}")
            if time.monotonic() - self.t_start > BOOT_LIMIT_S:
                self.kill()
                raise TimeoutError(f"server not listening after {BOOT_LIMIT_S:.0f} s; "
                                   f"log tail:\n{self.log_tail()}")
            time.sleep(0.2)
        return time.monotonic() - self.t_start

    def get(self, path: str, timeout: float = 60.0) -> dict:
        with urllib.request.urlopen(f"http://127.0.0.1:{self.port}{path}", timeout=timeout) as r:
            return json.loads(r.read())

    def stop(self) -> int | str:
        """SIGTERM, the drain, the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                return self.proc.wait(timeout=120.0)
            except subprocess.TimeoutExpired:
                self.kill()
                return "killed after 120 s"
        return self.proc.returncode

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
