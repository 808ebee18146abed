"""The system under test as a child process: start, wait until it can
serve, scrape, stop, destroy.  The process handling is chip_smoke.py's
(own session, `killpg` on every exit path, output to a log file), copied
here so that later changes to that script cannot change the yardstick.

Nothing here imports JAX or `raftsql_tpu`: a chip belongs to one
process, and that process is the engine.
"""
from __future__ import annotations

from http.client import HTTPConnection, HTTPException
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

# Every deadline of a run, in one place.  PR 21 saw 52-66 s from spawn to
# the first 204 on the chip when everything compiles; the first run in a
# checkout also builds the native WAL.  The contract allows that run
# 1200 s and a later one 360 s in all.
BOOT_DEADLINE_S = 420.0
STOP_DEADLINE_S = 60.0
SCRAPE_TIMEOUT_S = 30.0


class EngineFailure(Exception):
    """The engine did not do what a step of the run needs."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Engine:
    """One engine process (plus the HTTP workers it spawns) in `data_dir`."""

    def __init__(self, root: str, launcher: List[str], argv: List[str],
                 env: dict, data_dir: str, port: int):
        self.port = port
        self.data_dir = data_dir
        self.t_spawn = time.monotonic()
        self.log_path = os.path.join(data_dir, "engine.log")
        self._log = open(self.log_path, "ab")
        child_env = dict(os.environ)
        child_env.update(env)
        child_env["PYTHONPATH"] = root + os.pathsep + \
            child_env.get("PYTHONPATH", "")
        # Own session: destroy() takes the workers down with the engine
        # even when the engine is already gone.
        self.proc = subprocess.Popen(
            [sys.executable, *launcher, *argv, "--port", str(port)],
            cwd=data_dir, env=child_env, stdout=self._log,
            stderr=self._log, start_new_session=True)

    def get_doc(self, path: str, conn: Optional[HTTPConnection] = None
                ) -> dict:
        own = conn is None
        if own:
            conn = HTTPConnection("127.0.0.1", self.port,
                                  timeout=SCRAPE_TIMEOUT_S)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            body = resp.read()
        finally:
            if own:
                conn.close()
        if resp.status != 200:
            raise EngineFailure(f"GET {path} answered {resp.status}")
        return json.loads(body)

    def wait_ready(self, groups: int, platform: str, chips: int) -> dict:
        """Poll /healthz until it answers ready with EVERY group led, on
        the device the configuration asks for, with the native WAL.
        Returns the health document (without its per-group rows) plus
        the seconds since spawn at which /healthz first answered and at
        which the last group had a leader."""
        deadline = self.t_spawn + BOOT_DEADLINE_S
        up_s = None
        while True:
            if self.proc.poll() is not None:
                raise EngineFailure(
                    f"engine exited {self.proc.returncode} during start-up")
            if time.monotonic() > deadline:
                raise EngineFailure(
                    f"engine not ready {BOOT_DEADLINE_S:.0f} s after spawn")
            try:
                doc = self.get_doc("/healthz")
            except (OSError, HTTPException, EngineFailure, ValueError):
                time.sleep(0.5)
                continue
            if up_s is None:
                up_s = time.monotonic() - self.t_spawn
            rows = doc.pop("groups", {})
            led = sum(1 for row in rows.values() if row.get("leader", 0) > 0)
            if doc.get("ready") and len(rows) == groups and led == groups:
                break
            time.sleep(1.0)
        dev = doc.get("device", {})
        if dev.get("platform") != platform or dev.get("count") != chips:
            raise EngineFailure(
                f"engine runs on platform={dev.get('platform')!r} "
                f"count={dev.get('count')}; the cell needs {platform!r} "
                f"x{chips}")
        if not doc.get("native_wal"):
            raise EngineFailure("the native WAL did not load (the engine "
                                "fell back to the Python WAL)")
        doc["healthz_up_s"] = round(up_s, 2)
        doc["all_led_s"] = round(time.monotonic() - self.t_spawn, 2)
        return doc

    def stop(self) -> int:
        """SIGTERM and a bounded wait; the engine's exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=STOP_DEADLINE_S)
        except subprocess.TimeoutExpired:
            return -999

    def log_tail(self, n: int = 6000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")

    def destroy(self) -> None:
        """Kill whatever is left of the engine's process group."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()
