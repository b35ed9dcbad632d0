"""Minimal dashboard: HTTP state + metrics endpoints.

Reference analog: python/ray/dashboard/ (head.py:62 DashboardHead + the
modules/ API routes + metrics pipeline). Single-host collapse: one
aiohttp server exposing

  /api/tasks /api/actors /api/objects /api/nodes /api/placement_groups
  /api/summary /api/cluster_status   — JSON state (util/state.py)
  /metrics                           — Prometheus text (util/metrics.py)
  /timeline                          — Chrome trace JSON (task events)
  /api/trace[?trace_id=]             — task timeline merged with request
                                       spans (ray_tpu.obs flight recorder)
  /api/requests                      — flight-recorder trace listing
  /healthz                           — liveness

A React UI is out of scope; the JSON surface is the contract the
reference's UI consumes.
"""

from __future__ import annotations

import asyncio
import threading
from typing import Optional

from ray_tpu.utils.logging import get_logger

logger = get_logger("ray_tpu.dashboard")

_dashboard: Optional["Dashboard"] = None


class Dashboard:
    """`gcs_address` switches on the CLUSTER view: /api/cluster/* routes
    aggregate the GCS tables plus per-node stats pulled live from every
    node daemon's RPC server — each daemon IS the per-node dashboard
    agent (reference: dashboard/agent.py processes colocated with each
    raylet; here the daemon's rpc_stats/rpc_timeline endpoints fill that
    role without a separate process)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 8265,
                 gcs_address: Optional[str] = None):
        self.host = host
        self.port = port
        self.gcs_address = gcs_address
        self._started = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._serve, name="ray_tpu-dashboard", daemon=True
        )
        self._thread.start()
        if not self._started.wait(10):
            raise RuntimeError(f"dashboard failed to bind {host}:{port}")

    def _serve(self) -> None:
        from aiohttp import web

        from ray_tpu.util import metrics as metrics_mod
        from ray_tpu.util import state

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        def offload(fn, *args):
            return asyncio.get_event_loop().run_in_executor(None, fn, *args)

        async def healthz(_req):
            return web.Response(text="success")

        async def tasks(req):
            st = req.query.get("state")
            rows = await offload(lambda: [vars(r) for r in state.list_tasks(st)])
            return web.json_response(rows)

        async def actors(_req):
            return web.json_response(await offload(state.list_actors))

        async def objects(_req):
            return web.json_response(await offload(state.list_objects))

        async def nodes(_req):
            return web.json_response(await offload(state.list_nodes))

        async def pgs(_req):
            return web.json_response(await offload(state.list_placement_groups))

        async def summary(_req):
            return web.json_response(await offload(state.summarize_tasks))

        async def cluster_status(_req):
            import ray_tpu

            return web.json_response(
                {
                    "cluster_resources": await offload(ray_tpu.cluster_resources),
                    "available_resources": await offload(ray_tpu.available_resources),
                }
            )

        async def metrics(_req):
            return web.Response(
                text=metrics_mod.prometheus_text(),
                content_type="text/plain",
            )

        async def timeline(_req):
            return web.json_response(await offload(state.timeline))

        async def api_trace(req):
            """Request spans (ray_tpu.obs flight recorder) merged with the
            task timeline as one Chrome trace; ?trace_id= narrows
            both halves to one request. The response is BOUNDED
            (?limit=, default 50k events) with an explicit truncated flag
            — a runaway trace can't produce an export that nothing can
            ship or open."""
            trace_id = req.query.get("trace_id")
            try:
                limit = int(req.query.get("limit", 50_000))
            except ValueError:
                limit = 50_000

            def build():
                from ray_tpu.obs import get_recorder

                events = state.timeline()
                if trace_id:
                    events = [
                        e for e in events
                        if e.get("args", {}).get("trace_id") == trace_id
                    ]
                rec = get_recorder().chrome_trace_bounded(
                    trace_id=trace_id, max_events=limit
                )
                events += rec["events"]
                total = len(events) + (rec["total_spans"]
                                       - len(rec["events"]))
                truncated = rec["truncated"]
                if len(events) > limit:
                    events.sort(key=lambda e: e.get("ts", 0.0))
                    events = events[:limit]
                    truncated = True
                return {"events": events, "truncated": truncated,
                        "total_events": total}

            return web.json_response(await offload(build))

        async def api_requests(_req):
            from ray_tpu.obs import get_recorder

            return web.json_response(get_recorder().traces())

        # -- cluster view: GCS tables + live per-daemon agent stats --------
        # one cached connection per address (reference: rpc client pools);
        # per-request connect/teardown churn would spawn and abandon a
        # reader thread per node per poll
        from ray_tpu.cluster.rpc import ClientPool

        pool = ClientPool(timeout=5.0)
        self._pool = pool

        def _gcs_call(method, payload=None):
            host, port = self.gcs_address.rsplit(":", 1)
            return pool.get((host, int(port))).call(method, payload)

        def _node_call(n, method, payload=None):
            """One agent RPC; evict the cached connection on failure so a
            recovered daemon re-dials clean."""
            addr = tuple(n["addr"])
            try:
                return pool.get(addr).call(method, payload)
            except Exception:
                pool.invalidate(addr)
                raise

        def _agent_stats(n):
            try:  # the daemon doubles as the per-node agent
                n["stats"] = _node_call(n, "stats")
            except Exception as e:  # noqa: BLE001
                n["stats_error"] = repr(e)[:120]
            return n

        def _fan_out(nodes, fn):
            from concurrent.futures import ThreadPoolExecutor

            alive = [n for n in nodes if n.get("alive")]
            if not alive:
                return []
            # fan out: one wedged daemon must not serialize the sweep
            with ThreadPoolExecutor(max_workers=min(16, len(alive))) as ex:
                return list(ex.map(fn, alive))

        def _cluster_nodes():
            nodes = _gcs_call("list_nodes")
            _fan_out(nodes, _agent_stats)
            return nodes

        async def cluster_nodes(_req):
            return web.json_response(await offload(_cluster_nodes))

        async def cluster_actors(_req):
            rows = await offload(lambda: _gcs_call("list_actors"))
            for r in rows:
                r.pop("creation_spec", None)  # pickled blob, not JSON
            return web.json_response(_jsonable(rows))

        def _jsonable(x):
            if isinstance(x, bytes):
                return x.hex()
            if isinstance(x, dict):
                return {k: _jsonable(v) for k, v in x.items()}
            if isinstance(x, (list, tuple)):
                return [_jsonable(v) for v in x]
            return x

        async def cluster_pgs(_req):
            rows = await offload(lambda: _gcs_call("list_pgs"))
            return web.json_response(_jsonable(rows))

        async def cluster_demand(_req):
            return web.json_response(
                await offload(lambda: _gcs_call("cluster_demand"))
            )

        def _cluster_timeline():
            """Chrome-trace events of worker-side execution spans across
            all node daemons (the cross-process half of `ray timeline`;
            driver-side lease/exec spans live in the driver's client)."""

            import time as _time

            # bounded window: shipping each daemon's whole 20k-span
            # buffer per poll grows linearly with cluster size
            since = _time.time() - 600.0

            def pull(n):
                try:
                    return n["node_id"], _node_call(n, "timeline",
                                                    {"since": since})
                except Exception:  # noqa: BLE001
                    return n["node_id"], []

            events = []
            for node_id, spans in _fan_out(_gcs_call("list_nodes"), pull):
                for s in spans:
                    events.append({
                        "name": s.get("desc", "task"),
                        "ph": "X",
                        "ts": float(s.get("start", 0.0)) * 1e6,
                        "dur": max(
                            0.0,
                            float(s.get("end", 0.0)) - float(s.get("start", 0.0)),
                        ) * 1e6,
                        "pid": node_id,
                        "tid": s.get("worker_id", "worker"),
                        "cat": "exec" if s.get("ok", True) else "error",
                        **({"args": {"trace_id": s["trace_id"],
                                     "span_id": s.get("span_id")}}
                           if s.get("trace_id") else {}),
                    })
            return events

        async def cluster_timeline(_req):
            return web.json_response(await offload(_cluster_timeline))

        # -- telemetry plane (ray_tpu.obs.telemetry via the GCS store) -----

        async def api_metrics_cluster(_req):
            """Cluster-level aggregate: counter sums + rates, gauge
            rollups, merged histograms w/ percentiles, staleness."""
            return web.json_response(
                await offload(lambda: _gcs_call("telemetry_cluster"))
            )

        async def api_slo(_req):
            """Per-model-tag SLO grades from the MERGED TTFT/TPOT/queue
            histograms (the autoscaler's input)."""
            return web.json_response(
                await offload(lambda: _gcs_call("telemetry_slo"))
            )

        async def metrics_cluster(_req):
            """Merged Prometheus exposition: the fleet analog of each
            process's /metrics."""
            return web.Response(
                text=await offload(lambda: _gcs_call("telemetry_prometheus")),
                content_type="text/plain",
            )

        app = web.Application()
        app.router.add_get("/healthz", healthz)
        if self.gcs_address:
            app.router.add_get("/api/cluster/nodes", cluster_nodes)
            app.router.add_get("/api/cluster/actors", cluster_actors)
            app.router.add_get("/api/cluster/placement_groups", cluster_pgs)
            app.router.add_get("/api/cluster/demand", cluster_demand)
            app.router.add_get("/api/cluster/timeline", cluster_timeline)
            app.router.add_get("/api/metrics/cluster", api_metrics_cluster)
            app.router.add_get("/api/slo", api_slo)
            app.router.add_get("/metrics/cluster", metrics_cluster)
        app.router.add_get("/api/tasks", tasks)
        app.router.add_get("/api/actors", actors)
        app.router.add_get("/api/objects", objects)
        app.router.add_get("/api/nodes", nodes)
        app.router.add_get("/api/placement_groups", pgs)
        app.router.add_get("/api/summary", summary)
        app.router.add_get("/api/cluster_status", cluster_status)
        app.router.add_get("/metrics", metrics)
        app.router.add_get("/timeline", timeline)
        app.router.add_get("/api/trace", api_trace)
        app.router.add_get("/api/requests", api_requests)

        runner = web.AppRunner(app, access_log=None)

        async def _run():
            await runner.setup()
            site = web.TCPSite(runner, self.host, self.port)
            await site.start()
            self._started.set()
            while not self._stop.is_set():
                await asyncio.sleep(0.1)
            await runner.cleanup()

        try:
            loop.run_until_complete(_run())
        except Exception:
            logger.exception("dashboard crashed")
        finally:
            if getattr(self, "_pool", None) is not None:
                self._pool.close_all()
            loop.close()

    def shutdown(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)


def start_dashboard(host: str = "127.0.0.1", port: int = 8265,
                    gcs_address: Optional[str] = None) -> Dashboard:
    global _dashboard
    if _dashboard is None:
        _dashboard = Dashboard(host, port, gcs_address=gcs_address)
    return _dashboard


def shutdown_dashboard() -> None:
    global _dashboard
    if _dashboard is not None:
        _dashboard.shutdown()
        _dashboard = None
