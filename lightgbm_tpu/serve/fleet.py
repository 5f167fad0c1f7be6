"""Replica supervisor: one serve process is a single point of failure;
a supervised fleet is not.

``FleetSupervisor`` runs N replicas (shared-nothing serve stacks, each
pinning its own engine cache), probes ``/healthz``, and treats a dead
process and a hung-but-alive one identically: after
``fleet_fail_threshold`` consecutive failed probes (or immediately on
process exit) the replica is killed and restarted with exponential
backoff plus deterministic jitter.  ``fleet_circuit_failures``
consecutive failures open a circuit breaker — the slot leaves the
rotation and the fleet degrades gracefully instead of burning CPU on a
crash loop; after ``fleet_circuit_cooldown_s`` the circuit half-opens
and one restart is retried.

The supervisor is also the fleet's model-state reconciler: the desired
model (set by :meth:`FleetSupervisor.publish_model`, normally from the
checkpoint watcher) is swapped onto every healthy replica, and a
restarted replica — which comes back serving its original
``input_model`` — is re-swapped to the desired model BEFORE it rejoins
the rotation, so a crash mid-deploy cannot reintroduce an old version.

Replica handles come in two shapes behind one duck-typed interface
(``start() -> url``, ``alive()``, ``terminate(grace_s)``, ``kill()``):

- :class:`InprocReplica` — a full serve stack (Server + HTTP front) in
  daemon threads of THIS process; ``kill()`` closes the listening
  socket abruptly (no drain).  The unit-test replica: fast, and a kill
  looks exactly like a crash to probes and clients.
- :class:`ProcessReplica` — ``python -m lightgbm_tpu task=serve`` in a
  subprocess with ``serve_port=0`` + ``serve_port_file`` ephemeral-port
  discovery.  The chaos-harness replica (``tools/loadgen_serve.py
  --fleet``, the CI chaos job): ``kill()`` is a real SIGKILL,
  ``terminate()`` a SIGTERM that triggers the graceful drain.

Fault-injection point ``fleet.spawn`` (mode ``fail``) makes replica
spawn raise, exercising the backoff/circuit path deterministically.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from random import Random
from typing import Any, Callable, Dict, List, Optional

from ..obs import metrics as _obs_metrics
from ..obs import spans as _spans
from ..utils import faults as _faults
from ..utils.log import Log
from .config import FleetConfig, ServeConfig
from .registry import model_fingerprint


# ----------------------------------------------------------------------
# replica handles
# ----------------------------------------------------------------------
class InprocReplica:
    """A serve stack in this process's threads (unit-test replica)."""

    def __init__(self, booster=None, model_file: Optional[str] = None,
                 config: Optional[ServeConfig] = None):
        self._booster = booster
        self._model_file = model_file
        self._config = config or ServeConfig(port=0, batch_wait_ms=0.5,
                                             timeout_ms=30000)
        self.server = None
        self.httpd = None
        self.url: Optional[str] = None
        self._killed = False

    def start(self) -> str:
        from .http import serve_http
        from .server import Server
        self._config.port = 0
        self.server = Server(config=self._config)
        if self._booster is not None:
            self.server.registry.publish(self._booster)
        elif self._model_file:
            self.server.registry.publish(model_file=self._model_file)
        self.httpd, _ = serve_http(self.server, port=0, background=True)
        self.url = "http://127.0.0.1:%d" % self.httpd.server_address[1]
        return self.url

    def alive(self) -> bool:
        return not self._killed and self.httpd is not None

    def kill(self) -> None:
        """Crash simulation: the socket closes abruptly, in-flight
        connections reset, nothing drains."""
        self._killed = True
        httpd, server = self.httpd, self.server
        self.httpd = None
        if httpd is not None:
            try:
                httpd.shutdown()
                httpd.server_close()
            except Exception:              # noqa: BLE001 - already dead
                pass
        if server is not None:
            try:
                server.stop(timeout=1.0)
            except Exception:              # noqa: BLE001
                pass

    def terminate(self, grace_s: float = 10.0) -> None:
        """Graceful: drain admitted work, then close."""
        self._killed = True
        httpd, server = self.httpd, self.server
        self.httpd = None
        if server is not None:
            try:
                server.drain(grace_s)
            except Exception:              # noqa: BLE001
                pass
        if httpd is not None:
            try:
                httpd.shutdown()
                httpd.server_close()
            except Exception:              # noqa: BLE001
                pass


class ProcessReplica:
    """``python -m lightgbm_tpu task=serve`` in a subprocess."""

    def __init__(self, model_file: str, workdir: str, slot: int = 0,
                 params: Optional[Dict[str, Any]] = None,
                 env: Optional[Dict[str, str]] = None,
                 start_timeout_s: float = 120.0):
        self.model_file = str(model_file)
        self.workdir = str(workdir)
        self.slot = int(slot)
        self.params = dict(params or {})
        self.env = dict(env or {})
        self.start_timeout_s = float(start_timeout_s)
        self.proc: Optional[subprocess.Popen] = None
        self.url: Optional[str] = None
        self.log_path = os.path.join(self.workdir,
                                     f"replica_{self.slot}.log")

    def start(self) -> str:
        os.makedirs(self.workdir, exist_ok=True)
        port_file = os.path.join(
            self.workdir, f"replica_{self.slot}_{os.getpid()}.port")
        try:
            os.remove(port_file)
        except OSError:
            pass
        args = {"task": "serve", "input_model": self.model_file,
                "serve_port": "0", "serve_port_file": port_file}
        args.update({str(k): str(v) for k, v in self.params.items()})
        cmd = [sys.executable, "-m", "lightgbm_tpu"] + \
            [f"{k}={v}" for k, v in args.items()]
        env = dict(os.environ)
        # a replica only scores, and the predict and SHAP engines
        # compute on the host CPU (ops/predict.py engine_device): pin
        # its platform so it never takes a chip it would not use — a
        # chip belongs to one process.  ``self.env`` may override.
        env["JAX_PLATFORMS"] = "cpu"
        # propagate the active trace (if a span is open — e.g. the
        # supervisor restarting a replica during a publish) so the
        # replica can mark its boot against it (obs/spans.py)
        env.update(_spans.env_carrier())
        env.update(self.env)
        Log.info("fleet: replica %d starts with JAX_PLATFORMS=%s",
                 self.slot, env["JAX_PLATFORMS"])
        log = open(self.log_path, "ab")
        try:
            self.proc = subprocess.Popen(cmd, stdout=log, stderr=log,
                                         env=env, cwd=self.workdir)
        finally:
            log.close()
        deadline = time.monotonic() + self.start_timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {self.slot} exited rc={self.proc.returncode}"
                    f" during startup (log: {self.log_path})")
            if os.path.isfile(port_file):
                try:
                    with open(port_file) as f:
                        port = int(f.read().strip())
                    self.url = f"http://127.0.0.1:{port}"
                    return self.url
                except (OSError, ValueError):
                    pass                   # torn read; retry
            time.sleep(0.05)
        self.kill()
        raise RuntimeError(f"replica {self.slot} did not report a port "
                           f"within {self.start_timeout_s:.0f}s "
                           f"(log: {self.log_path})")

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass

    def terminate(self, grace_s: float = 10.0) -> None:
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.terminate()              # SIGTERM -> graceful drain
        try:
            self.proc.wait(timeout=max(grace_s, 0.1))
        except subprocess.TimeoutExpired:
            self.kill()


# ----------------------------------------------------------------------
# supervisor
# ----------------------------------------------------------------------
class _Slot:
    __slots__ = ("index", "handle", "url", "state", "probe_fails",
                 "failures", "next_restart_at", "start_deadline",
                 "opened_at", "in_rotation", "health_model_id",
                 "health_models", "draining")

    def __init__(self, index: int):
        self.index = index
        self.handle = None
        self.url: Optional[str] = None
        self.state = "new"    # new|starting|healthy|backoff|circuit_open
        self.probe_fails = 0
        self.failures = 0     # consecutive, reset on a healthy probe
        self.next_restart_at = 0.0
        self.start_deadline = 0.0
        self.opened_at = 0.0
        self.in_rotation = False
        self.health_model_id: Optional[str] = None
        # per-tenant fingerprints from the last /healthz body (the
        # ``models`` map) — what reconciliation and endpoints() compare
        # against the fleet's desired set
        self.health_models: Dict[str, Optional[str]] = {}
        # the last probe answered 503 {"draining": true}: deliberately
        # finishing admitted work, must not be routed to OR killed
        self.draining = False


class FleetSupervisor:
    """Supervises N replica slots; see the module docstring."""

    def __init__(self, factory: Callable[[int], Any],
                 config: Optional[FleetConfig] = None,
                 recorder=None):
        self.factory = factory
        self.config = config or FleetConfig()
        self.config.validate()
        self.recorder = recorder
        self._slots = [_Slot(i) for i in range(self.config.replicas)]
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # desired model state per tenant name: {name: (model_id,
        # model_text)}.  The single-model API (publish_model with no
        # name) lives under the "default" tenant.
        self._desired: Dict[str, tuple] = {}
        # a router fronting this fleet (set_router): its ltpu_router_*
        # registry series join the aggregate scrape
        self._router = None

    # -- telemetry -----------------------------------------------------
    def _emit(self, event: str, **fields) -> None:
        if self.recorder is not None:
            self.recorder.emit("fleet", event=event, **fields)

    # -- lifecycle -----------------------------------------------------
    def start(self, wait_healthy_s: float = 0.0) -> "FleetSupervisor":
        for slot in self._slots:
            self._spawn(slot, time.monotonic())
        self._thread = threading.Thread(target=self._monitor,
                                        name="ltpu-fleet", daemon=True)
        self._thread.start()
        if wait_healthy_s > 0:
            deadline = time.monotonic() + wait_healthy_s
            while time.monotonic() < deadline:
                if len(self.endpoints()) == len(self._slots):
                    break
                time.sleep(0.05)
        return self

    def stop(self, grace_s: Optional[float] = None) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        grace = 10.0 if grace_s is None else grace_s
        for slot in self._slots:
            if slot.handle is not None:
                try:
                    slot.handle.terminate(grace)
                except Exception:          # noqa: BLE001
                    pass
                slot.handle = None
            slot.in_rotation = False

    # -- introspection / routing --------------------------------------
    def _routable(self, slot: _Slot) -> bool:
        """Caller holds the lock.  A slot is routable only when its
        last probe was healthy and non-draining AND every desired
        tenant's fingerprint matches the replica's last-reported one —
        so a mid-drain or stale-model replica never reaches clients,
        even in the window between publish_model setting the desired
        state and the per-slot swaps landing."""
        if not (slot.in_rotation and slot.url) or slot.draining:
            return False
        for name, (mid, _text) in self._desired.items():
            if slot.health_models.get(name) != mid:
                return False
        return True

    def endpoints(self) -> List[str]:
        """Base URLs of routable replicas: healthy, not draining, and
        serving every desired tenant's CURRENT fingerprint — so even
        clients that round-robin this list themselves never hit a
        mid-deploy or mid-drain replica."""
        with self._lock:
            return [s.url for s in self._slots if self._routable(s)]

    def slots(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{"index": s.index, "state": s.state, "url": s.url,
                     "failures": s.failures,
                     "in_rotation": s.in_rotation,
                     "draining": s.draining,
                     "model_id": s.health_model_id,
                     "models": dict(s.health_models)}
                    for s in self._slots]

    def handle(self, index: int):
        return self._slots[index].handle

    def replica_count(self) -> int:
        with self._lock:
            return len(self._slots)

    # -- elastic capacity (serve/autoscaler.py) ------------------------
    def scale_to(self, n: int, reason: str = "") -> int:
        """Resize the fleet to ``n`` slots.  Growing appends fresh
        slots and spawns them immediately; draining retires the
        highest-index slots with a graceful ``terminate`` (admitted
        work finishes — the drain semantics clients never notice) in a
        background thread so the caller's control loop is not blocked
        on the drain grace.  Returns the new slot count."""
        n = int(n)
        if n < 1:
            raise ValueError("scale_to needs n >= 1")
        now = time.monotonic()
        with self._lock:
            cur = len(self._slots)
            if n == cur:
                return cur
            if n > cur:
                added = [_Slot(i) for i in range(cur, n)]
                self._slots.extend(added)
                removed = []
            else:
                added = []
                removed = self._slots[n:]
                del self._slots[n:]
                for slot in removed:
                    slot.in_rotation = False
        self._emit("scale", direction="grow" if added else "drain",
                   from_replicas=cur, to_replicas=n,
                   reason=str(reason)[:120])
        Log.info("fleet: scale %d -> %d replicas (%s)", cur, n,
                 reason or "operator")
        for slot in added:
            self._spawn(slot, now)
        if removed:
            def _retire(slots=removed):
                for slot in slots:
                    handle = slot.handle
                    slot.handle = None
                    if handle is not None:
                        try:
                            handle.terminate(10.0)
                        except Exception:  # noqa: BLE001
                            pass
            threading.Thread(target=_retire, name="ltpu-fleet-drain",
                             daemon=True).start()
        return n

    def active_models(self, model: str = "default"
                      ) -> Dict[int, Optional[str]]:
        """Last-probed fingerprint of one tenant per healthy slot."""
        with self._lock:
            return {s.index: s.health_models.get(
                        model, s.health_model_id if model == "default"
                        else None)
                    for s in self._slots if s.state == "healthy"}

    def desired_fingerprint(self, model: str = "default"
                            ) -> Optional[str]:
        """The fingerprint the named tenant is converging onto (what a
        router tier filters stale replicas against), or None before
        any publish."""
        with self._lock:
            d = self._desired.get(model)
            return d[0] if d else None

    # -- model state ---------------------------------------------------
    def publish_model(self, model_text: str, source: str = "",
                      model: str = "default") -> str:
        """Set the named tenant's desired model and swap every healthy
        replica now; the monitor re-swaps stragglers and restarted
        replicas until the whole fleet converges."""
        mid = model_fingerprint(model_text)
        with self._lock:
            self._desired[model] = (mid, model_text)
            targets = [(s, s.url) for s in self._slots
                       if s.state == "healthy" and s.url]
        # once _desired is set the publish cannot fail as a whole: a
        # slot whose swap misses here (crash race, transport error) is
        # reconciled by the monitor, so the caller never sees an
        # exception for a model the fleet is already converging onto
        for slot, url in targets:
            try:
                self._swap_slot(slot, model, mid, model_text, url)
            except Exception as exc:       # noqa: BLE001 - reconciled
                Log.warning("fleet: replica %d swap errored: %s",
                            slot.index, exc)
                with self._lock:
                    slot.in_rotation = False
        return mid

    def _swap_slot(self, slot: _Slot, name: str, mid: str, text: str,
                   url: Optional[str] = None) -> bool:
        url = url or slot.url
        if url is None:                    # crashed since being listed
            with self._lock:
                slot.in_rotation = False
            return False
        # the X-Ltpu-Trace carrier makes the replica's swap (and the
        # first request the new version serves) join the publish trace
        path = "/swap" if name == "default" else f"/v1/{name}/swap"
        st, out = _post_json(url, path, {"model_str": text},
                             timeout=60,
                             headers=_spans.http_headers())
        if st == 200 and out.get("model_id") == mid:
            with self._lock:
                slot.health_models[name] = mid
                if name == "default":
                    slot.health_model_id = mid
                slot.in_rotation = slot.state == "healthy"
            return True
        Log.warning("fleet: replica %d swap of %r failed (HTTP %s: %s)",
                    slot.index, name, st,
                    str(out.get("error", ""))[:120])
        with self._lock:
            slot.in_rotation = False       # stale model: out of rotation
        return False

    # -- aggregate telemetry probe ------------------------------------
    def stats_probe(self) -> Dict[str, float]:
        """Aggregate serve rollups across reachable replicas, the
        rollback controller's instrument: cumulative request/bad
        counts (bad = shed + timeout + error; rejected is the fleet
        doing its backpressure job) and the worst per-replica p99."""
        total, bad, p99 = 0, 0, 0.0
        with self._lock:
            urls = [s.url for s in self._slots
                    if s.state == "healthy" and s.url]
        for url in urls:
            try:
                with urllib.request.urlopen(
                        url + "/stats",
                        timeout=self.config.probe_timeout_s) as r:
                    s = json.loads(r.read())
            except Exception:              # noqa: BLE001 - probe only
                continue
            counts = s.get("requests") or {}
            total += sum(int(v) for v in counts.values())
            bad += sum(int(counts.get(k, 0))
                       for k in ("shed", "timeout", "error"))
            p99 = max(p99, float((s.get("latency_ms") or {})
                                 .get("p99", 0.0)))
        return {"requests": float(total), "bad": float(bad),
                "p99_ms": p99}

    # -- fleet-level metrics aggregation -------------------------------
    def set_router(self, router) -> None:
        """Attach the router fronting this fleet: its own
        ``ltpu_router_*`` (and ``ltpu_slo_*``) series join
        :meth:`metrics_text` as a ``replica="router"`` scrape — one
        pane of glass for the whole serve tier."""
        self._router = router

    def metrics_text(self) -> str:
        """One Prometheus exposition for the whole fleet: every
        reachable replica's ``GET /metrics`` scrape re-labeled with
        ``replica="<slot>"`` plus supervisor-level gauges (slot
        states, desired model) — the scrape surface a router tier in
        front of :meth:`endpoints` consumes
        (``docs/Observability.md``).  With :meth:`set_router`, the
        router's own series ride along as ``replica="router"``."""
        with self._lock:
            targets = [(s.index, s.url) for s in self._slots
                       if s.state == "healthy" and s.url]
            states = [(s.index, s.state, s.in_rotation)
                      for s in self._slots]
            desired = dict(self._desired)
            router = self._router
        scrapes = []
        if router is not None:
            try:
                scrapes.append(("router", _filter_families(
                    router.metrics_text(),
                    ("ltpu_router_", "ltpu_slo_"))))
            except Exception:              # noqa: BLE001 - probe only
                pass
        for index, url in targets:
            try:
                with urllib.request.urlopen(
                        url + "/metrics",
                        timeout=self.config.probe_timeout_s) as r:
                    scrapes.append((str(index), r.read().decode()))
            except Exception:              # noqa: BLE001 - probe only
                continue
        lines = [
            "# HELP ltpu_fleet_replicas configured replica slots",
            "# TYPE ltpu_fleet_replicas gauge",
            f"ltpu_fleet_replicas {len(states)}",
            "# HELP ltpu_fleet_in_rotation slots currently routable",
            "# TYPE ltpu_fleet_in_rotation gauge",
            f"ltpu_fleet_in_rotation "
            f"{sum(1 for _, _, rot in states if rot)}",
            "# HELP ltpu_fleet_slot_state per-slot supervisor state "
            "(1 = the labeled state is current)",
            "# TYPE ltpu_fleet_slot_state gauge",
        ]
        for index, state, _rot in states:
            lines.append('ltpu_fleet_slot_state{slot="%d",state="%s"}'
                         ' 1' % (index, state))
        if desired:
            lines += [
                "# HELP ltpu_fleet_desired_model_info desired model "
                "fingerprint per tenant (value always 1)",
                "# TYPE ltpu_fleet_desired_model_info gauge",
            ]
            for name in sorted(desired):
                lines.append(
                    'ltpu_fleet_desired_model_info{model="%s",'
                    'model_id="%s"} 1' % (name, desired[name][0]))
        return "\n".join(lines) + "\n" + _obs_metrics.aggregate(scrapes)

    # -- monitor -------------------------------------------------------
    def _backoff_s(self, slot: _Slot) -> float:
        n = max(slot.failures, 1)
        base = min(self.config.backoff_base_s * (2 ** (n - 1)),
                   self.config.backoff_max_s)
        # deterministic jitter: seeded by (seed, slot, attempt) so a
        # herd of replicas spreads out, yet tests replay exactly
        u = Random(self.config.seed * 1_000_003
                   + slot.index * 1009 + n).random()
        return base * (1.0 + self.config.backoff_jitter * u)

    def _spawn(self, slot: _Slot, now: float) -> None:
        try:
            mode = _faults.fire("fleet.spawn")
            if mode == "fail":
                raise RuntimeError("injected fault (fleet.spawn:fail)")
            handle = self.factory(slot.index)
            url = handle.start()
        except BaseException as exc:       # InjectedFault included
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            Log.warning("fleet: replica %d spawn failed: %s",
                        slot.index, exc)
            self._fail(slot, now, cause=f"spawn: {exc}")
            return
        with self._lock:
            slot.handle = handle
            slot.url = url
            slot.state = "starting"
            slot.probe_fails = 0
            slot.start_deadline = now + max(
                10 * self.config.probe_interval_s, 5.0)
        self._emit("replica_start", slot=slot.index, url=url)
        Log.info("fleet: replica %d up at %s", slot.index, url)

    def _fail(self, slot: _Slot, now: float, cause: str) -> None:
        handle = slot.handle
        with self._lock:
            slot.handle = None
            slot.url = None
            slot.in_rotation = False
            slot.health_model_id = None
            slot.health_models = {}
            slot.draining = False
            slot.failures += 1
            failures = slot.failures
        if handle is not None:
            try:
                handle.kill()
            except Exception:              # noqa: BLE001
                pass
        self._emit("replica_exit", slot=slot.index, cause=cause[:200],
                   failures=failures)
        if failures >= self.config.circuit_failures:
            with self._lock:
                slot.state = "circuit_open"
                slot.opened_at = now
            self._emit("circuit_open", slot=slot.index,
                       failures=failures)
            Log.warning("fleet: replica %d circuit OPEN after %d "
                        "consecutive failures — slot leaves the "
                        "rotation", slot.index, failures)
            return
        backoff = self._backoff_s(slot)
        with self._lock:
            slot.state = "backoff"
            slot.next_restart_at = now + backoff
        self._emit("replica_restart", slot=slot.index, attempt=failures,
                   backoff_ms=round(backoff * 1e3, 1))
        Log.info("fleet: replica %d restart #%d in %.2fs (%s)",
                 slot.index, failures, backoff, cause[:120])

    def _probe(self, url: str):
        try:
            with urllib.request.urlopen(
                    url + "/healthz",
                    timeout=self.config.probe_timeout_s) as r:
                obj = json.loads(r.read())
            return bool(obj.get("ok")), obj
        except urllib.error.HTTPError as e:
            # a non-200 /healthz still carries a body — a draining
            # replica answers 503 {"draining": true}, which _tick must
            # distinguish from a hang
            try:
                return False, json.loads(e.read())
            except Exception:              # noqa: BLE001 - probe only
                return False, None
        except Exception:                  # noqa: BLE001 - probe only
            return False, None

    def _tick(self) -> None:
        now = time.monotonic()
        with self._lock:
            # scale_to may resize the slot list concurrently
            slots = list(self._slots)
        for slot in slots:
            state = slot.state
            if state == "circuit_open":
                cd = self.config.circuit_cooldown_s
                if cd > 0 and now - slot.opened_at >= cd:
                    with self._lock:
                        slot.state = "backoff"
                        slot.next_restart_at = now
                    self._emit("circuit_half_open", slot=slot.index)
                continue
            if state == "backoff":
                if now >= slot.next_restart_at:
                    self._spawn(slot, now)
                continue
            if state not in ("starting", "healthy"):
                continue
            handle, url = slot.handle, slot.url
            if handle is None or url is None:
                continue
            if not handle.alive():
                self._fail(slot, now, cause="process exited")
                continue
            ok, health = self._probe(url)
            if ok:
                body = health or {}
                with self._lock:
                    slot.probe_fails = 0
                    slot.failures = 0
                    slot.state = "healthy"
                    slot.draining = False
                    slot.health_model_id = body.get("model_id")
                    models = body.get("models")
                    slot.health_models = dict(models) \
                        if isinstance(models, dict) else \
                        {"default": body.get("model_id")}
                    stale = [(n, d) for n, d in self._desired.items()
                             if slot.health_models.get(n) != d[0]]
                if stale:
                    # reconcile: restarted/straggler replica still on
                    # an old model (for ANY tenant) rejoins only once
                    # every stale tenant is re-swapped
                    for name, (mid, text) in stale:
                        if not self._swap_slot(slot, name, mid, text):
                            break
                else:
                    with self._lock:
                        slot.in_rotation = True
                continue
            if health is not None and health.get("draining"):
                # graceful drain in progress (operator SIGTERM): the
                # replica is deliberately finishing admitted work.
                # Stop routing to it, but do NOT count probes toward a
                # kill — SIGKILLing it now would drop the very
                # requests the drain protects.  The restart rides the
                # normal process-exit path once the drain completes.
                with self._lock:
                    slot.in_rotation = False
                    slot.draining = True
                    slot.probe_fails = 0
                    slot.health_model_id = None
                    slot.health_models = {}
                continue
            if state == "starting":
                if now > slot.start_deadline:
                    self._fail(slot, now, cause="never became healthy")
                continue
            with self._lock:
                slot.probe_fails += 1
                fails = slot.probe_fails
                slot.in_rotation = False   # failing probes: stop routing
            if fails >= self.config.fail_threshold:
                self._fail(slot, now,
                           cause=f"{fails} consecutive failed probes "
                                 f"(hung?)")

    def _monitor(self) -> None:
        while not self._stop.wait(self.config.probe_interval_s):
            try:
                self._tick()
            except Exception as exc:       # noqa: BLE001 - keep going
                Log.warning("fleet: monitor tick failed: %s", exc)


def _filter_families(text: str, prefixes) -> str:
    """Keep only the metric families whose name starts with one of
    ``prefixes`` from a Prometheus exposition — the router process's
    registry also carries fleet-irrelevant series the aggregate must
    not duplicate."""
    out: List[str] = []
    for line in text.splitlines():
        s = line.strip()
        if not s:
            continue
        if s.startswith("# "):
            parts = s.split(None, 3)
            name = parts[2] if len(parts) >= 3 else ""
        else:
            name = s.split("{", 1)[0].split(None, 1)[0]
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
                break
        if any(base.startswith(p) or name.startswith(p)
               for p in prefixes):
            out.append(s)
    return "\n".join(out) + "\n"


def _post_json(url: str, path: str, obj: Dict[str, Any],
               timeout: float = 30.0,
               headers: Optional[Dict[str, str]] = None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url + path, data=json.dumps(obj).encode(), headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except ValueError:
            return e.code, {"error": "unparseable body"}
    except (urllib.error.URLError, OSError) as e:
        return 599, {"error": f"transport: {e}"}
