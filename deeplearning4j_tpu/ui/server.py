"""Live training dashboard server (ref: org.deeplearning4j.ui.api.UIServer /
VertxUIServer in deeplearning4j-ui — `UIServer.getInstance().attach(storage)`
then browse the train overview while fit() runs).

The reference embeds a Vert.x web server pushing SBE stats over websockets to
JS charts. The rebuild serves the same overview — score, learning rate,
update:param ratio (log10), iteration time — from a stdlib
``ThreadingHTTPServer`` with a polling JSON API (no websockets, no
dependencies; a 1 s poll is indistinguishable for training telemetry):

  GET  /                               overview page (vanilla-JS canvas charts)
  GET  /api/sessions                   [{sessionId, workers, info}, ...]
  GET  /api/updates/<sid>/<worker>?from=N   reports N.. (incremental poll)
  POST /remote/receive                 remote stats routing (see below)

``RemoteStatsStorageRouter`` is the write-side client (ref:
RemoteUIStatsStorageRouter): a StatsListener in another process (e.g. a
multi-host worker, SURVEY §2.10 control plane) posts its reports to a central
UI server over HTTP instead of writing a local file.
"""
from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
import warnings
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional
from urllib.parse import parse_qs, urlparse

from deeplearning4j_tpu.ui.stats import StatsListener  # noqa: F401 (re-export convenience)
from deeplearning4j_tpu.ui.palette import PALETTE
from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage, StatsStorage

_STYLE = """<style>
 body { font-family: system-ui, sans-serif; margin: 24px; color: #222; }
 h1 { font-size: 20px; } h2 { font-size: 14px; margin: 0 0 4px; }
 .meta { color: #666; font-size: 13px; margin-bottom: 14px; }
 .grid { display: flex; flex-wrap: wrap; gap: 18px; }
 .panel { border: 1px solid #ddd; border-radius: 6px; padding: 10px; }
 select { margin-bottom: 12px; }
 nav { margin-bottom: 16px; font-size: 14px; }
 nav a { margin-right: 14px; color: #06c; text-decoration: none; }
 nav a.here { color: #222; font-weight: 600; }
 .node { border: 1px solid #bbb; border-radius: 4px; padding: 6px 10px;
         margin: 4px 0; cursor: pointer; font-size: 13px; background: #fafafa; }
 .node.sel { border-color: #06c; background: #eef5ff; }
 .node .k { color: #888; font-size: 11px; }
 .arrow { text-align: center; color: #999; font-size: 11px; }
 table.kv { border-collapse: collapse; font-size: 13px; }
 table.kv td { border: 1px solid #ddd; padding: 4px 10px; }
</style>"""

_NAV = """<nav><a href="/" class="%(ov)s">Overview</a>
<a href="/model" class="%(mo)s">Model</a>
<a href="/system" class="%(sy)s">System</a></nav>"""


def _nav(which: str) -> str:
    return _NAV % {k: ("here" if k == which else "")
                   for k in ("ov", "mo", "sy")}


_PAGE = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>deeplearning4j_tpu — training</title>
__STYLE__</head><body>
__NAV__
<h1>Training overview</h1>
<div class="meta" id="meta">waiting for sessions…</div>
<select id="session"></select>
<div class="grid">
 <div class="panel"><h2>Score</h2><canvas id="score" width="440" height="170"></canvas></div>
 <div class="panel"><h2>Learning rate</h2><canvas id="lr" width="440" height="170"></canvas></div>
 <div class="panel"><h2>Update:param ratio (log10)</h2><canvas id="ratio" width="440" height="170"></canvas></div>
 <div class="panel"><h2>Iteration time (ms)</h2><canvas id="dur" width="440" height="170"></canvas></div>
</div>
<script>
__COMMON__
function render(fresh) {
  document.getElementById('meta').textContent =
    `${cur} · ${curInfo.modelClass || '?'} · ${curInfo.numParams ?? '?'} params · ` +
    `${curInfo.backend || '?'} · ${reports.length} reports`;
  if (!fresh) return;
  const it = r => r.iteration;
  drawLines('score', {score: reports.map(r => [it(r), r.score])});
  drawLines('lr', {lr: reports.filter(r => r.learningRate != null).map(r => [it(r), r.learningRate])});
  drawLines('dur', {ms: reports.filter(r => r.durationMs != null).map(r => [it(r), r.durationMs])});
  const names = new Set();
  for (const r of reports) for (const n of Object.keys(r.updateRatios || {})) names.add(n);
  const ratio = {};
  for (const n of Array.from(names).sort().slice(0, 8))
    ratio[n] = reports.filter(r => (r.updateRatios || {})[n] > 0)
                      .map(r => [it(r), Math.log10(r.updateRatios[n])]);
  drawLines('ratio', ratio);
}
</script></body></html>"""


# Shared JS for all tabs: line/bar chart renderers plus the session poller;
# each page provides a render(fresh) callback over (cur, curInfo, reports).
_COMMON_JS = """
let cur = null, reports = [], nextFrom = 0, curInfo = {};
const COLORS = __PALETTE__;
function drawLines(id, seriesMap) {
  const cv = document.getElementById(id), ctx = cv.getContext('2d');
  ctx.clearRect(0, 0, cv.width, cv.height);
  const pad = 34, W = cv.width, H = cv.height;
  let lo = Infinity, hi = -Infinity, x0 = Infinity, x1 = -Infinity;
  for (const pts of Object.values(seriesMap)) for (const [x, y] of pts) {
    if (!isFinite(y)) continue;
    lo = Math.min(lo, y); hi = Math.max(hi, y);
    x0 = Math.min(x0, x); x1 = Math.max(x1, x);
  }
  if (!isFinite(lo)) return;
  if (hi === lo) hi = lo + 1e-9; if (x1 === x0) x1 = x0 + 1;
  ctx.font = '10px sans-serif'; ctx.fillStyle = '#555';
  ctx.fillText(hi.toPrecision(3), 2, pad); ctx.fillText(lo.toPrecision(3), 2, H - pad);
  ctx.fillText(String(x0), pad, H - 6); ctx.fillText(String(x1), W - pad - 20, H - 6);
  let ci = 0;
  for (const [name, pts] of Object.entries(seriesMap)) {
    ctx.strokeStyle = COLORS[ci++ % COLORS.length]; ctx.beginPath();
    let first = true;
    for (const [x, y] of pts) {
      if (!isFinite(y)) continue;
      const px = pad + (x - x0) / (x1 - x0) * (W - 2 * pad);
      const py = H - pad - (y - lo) / (hi - lo) * (H - 2 * pad);
      if (first) { ctx.moveTo(px, py); first = false; } else ctx.lineTo(px, py);
    }
    ctx.stroke();
  }
}
function drawBars(id, hist) {
  const cv = document.getElementById(id), ctx = cv.getContext('2d');
  ctx.clearRect(0, 0, cv.width, cv.height);
  if (!hist || !hist.counts || !hist.counts.length) return;
  const pad = 30, W = cv.width, H = cv.height;
  const mx = Math.max(...hist.counts, 1), n = hist.counts.length;
  ctx.fillStyle = COLORS[0];
  for (let i = 0; i < n; i++) {
    const h = hist.counts[i] / mx * (H - 2 * pad);
    const bw = (W - 2 * pad) / n;
    ctx.fillRect(pad + i * bw, H - pad - h, Math.max(bw - 1, 1), h);
  }
  ctx.font = '10px sans-serif'; ctx.fillStyle = '#555';
  ctx.fillText(hist.min.toPrecision(3), pad, H - 8);
  ctx.fillText(hist.max.toPrecision(3), W - pad - 34, H - 8);
}
async function poll() {
  try {
    const sessions = await (await fetch('api/sessions')).json();
    const sel = document.getElementById('session');
    const ids = sessions.map(s => s.sessionId);
    const have = Array.from(sel.options).map(o => o.value);
    if (ids.length !== have.length || ids.some((id, i) => id !== have[i])) {
      const keep = sel.value;
      sel.replaceChildren(...ids.map(id => {
        const o = document.createElement('option');
        o.textContent = id;
        return o;
      }));
      if (ids.includes(keep)) sel.value = keep;
    }
    if (!sessions.length) return;
    const sid = sel.value || sessions[0].sessionId;
    const s = sessions.find(x => x.sessionId === sid) || sessions[0];
    if (cur !== sid) { cur = sid; reports = []; nextFrom = 0; }
    curInfo = s.info || {};
    const worker = s.workers[0];
    const fresh = await (await fetch(
      `api/updates/${sid}/${worker}?from=${nextFrom}`)).json();
    if (fresh.length) { reports = reports.concat(fresh); nextFrom += fresh.length; }
    render(fresh.length > 0);
  } catch (e) { /* server restarting — keep polling */ }
}
setInterval(poll, 1000); poll();
""".replace("__PALETTE__", json.dumps(PALETTE))

_PAGE = _PAGE.replace("__COMMON__", _COMMON_JS) \
    .replace("__STYLE__", _STYLE).replace("__NAV__", _nav("ov"))


_MODEL_PAGE = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>deeplearning4j_tpu — model</title>
__STYLE__</head><body>
__NAV__
<h1>Model graph</h1>
<div class="meta" id="meta">waiting for sessions…</div>
<select id="session"></select>
<div style="display:flex; gap:24px; align-items:flex-start">
 <div id="graph" style="min-width:230px"></div>
 <div class="grid" id="layerPanels" style="display:none; flex-wrap:wrap">
  <div class="panel"><h2>Param mean magnitude</h2><canvas id="pmm" width="420" height="160"></canvas></div>
  <div class="panel"><h2>Gradient mean magnitude</h2><canvas id="gmm" width="420" height="160"></canvas></div>
  <div class="panel"><h2>Update:param ratio (log10)</h2><canvas id="upr" width="420" height="160"></canvas></div>
  <div class="panel"><h2>Param histogram (latest)</h2><canvas id="phist" width="420" height="160"></canvas></div>
 </div>
</div>
<script>
__COMMON__
let selNode = null, builtFor = null;
function layerSeries(prefix, field) {
  // stats keys are '<nodeId>/<leaf>' — join per-leaf series for this node
  const out = {};
  for (const r of reports) {
    for (const [k, st] of Object.entries(r[field] || {})) {
      if (k.split('/')[0] !== prefix) continue;
      (out[k] = out[k] || []).push([r.iteration, st.meanMagnitude]);
    }
  }
  return out;
}
function ratioSeries(prefix) {
  const out = {};
  for (const r of reports) {
    for (const [k, v] of Object.entries(r.updateRatios || {})) {
      if (k.split('/')[0] !== prefix || !(v > 0)) continue;
      (out[k] = out[k] || []).push([r.iteration, Math.log10(v)]);
    }
  }
  return out;
}
function latestHist(prefix) {
  for (let i = reports.length - 1; i >= 0; i--) {
    for (const [k, h] of Object.entries(reports[i].parameterHistograms || {}))
      if (k.split('/')[0] === prefix) return h;
  }
  return null;
}
function buildGraph(topo) {
  const g = document.getElementById('graph');
  g.replaceChildren();
  if (!topo) { g.textContent = 'no topology for this model type'; return; }
  const byId = {};
  topo.nodes.forEach(n => byId[n.id] = n);
  topo.nodes.forEach((n, i) => {
    const ins = topo.edges.filter(e => e[1] === n.id).map(e => e[0]);
    if (i > 0) {
      // draw the chain arrow only for a REAL edge from the node above;
      // branching graphs get a plain gap + the explicit fan-in list below
      const a = document.createElement('div');
      a.className = 'arrow';
      a.textContent = ins.includes(topo.nodes[i - 1].id) ? '\\u2193' : '\\u00b7';
      g.appendChild(a);
    }
    const d = document.createElement('div');
    d.className = 'node'; d.dataset.id = n.id;
    const t = document.createElement('div'); t.textContent = n.label +
      (n.nOut ? ` (nOut=${n.nOut})` : '');
    const k = document.createElement('div'); k.className = 'k';
    k.textContent = n.id + (ins.length ? ' \\u2190 ' + ins.join(', ') : '');
    d.appendChild(t); d.appendChild(k);
    d.onclick = () => { selNode = n.id; render(true); };
    g.appendChild(d);
  });
}
function render(fresh) {
  document.getElementById('meta').textContent =
    `${cur} · ${curInfo.modelClass || '?'} · ${curInfo.numParams ?? '?'} params`;
  if (builtFor !== cur) { builtFor = cur; selNode = null; buildGraph(curInfo.topology); }
  document.querySelectorAll('.node').forEach(d =>
    d.classList.toggle('sel', d.dataset.id === selNode));
  const panels = document.getElementById('layerPanels');
  panels.style.display = selNode == null ? 'none' : 'flex';
  if (selNode == null || !fresh) return;
  drawLines('pmm', layerSeries(selNode, 'parameterStats'));
  drawLines('gmm', layerSeries(selNode, 'gradientStats'));
  drawLines('upr', ratioSeries(selNode));
  drawBars('phist', latestHist(selNode));
}
</script></body></html>""".replace("__COMMON__", _COMMON_JS) \
    .replace("__STYLE__", _STYLE).replace("__NAV__", _nav("mo"))


_SYSTEM_PAGE = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>deeplearning4j_tpu — system</title>
__STYLE__</head><body>
__NAV__
<h1>System</h1>
<div class="meta" id="meta">waiting for sessions…</div>
<select id="session"></select>
<table class="kv" id="static"></table><br>
<div class="grid">
 <div class="panel"><h2>Host memory RSS (MB)</h2><canvas id="rss" width="440" height="170"></canvas></div>
 <div class="panel"><h2>Device memory in use (MB)</h2><canvas id="dev" width="440" height="170"></canvas></div>
 <div class="panel"><h2>Iteration time (ms)</h2><canvas id="dur" width="440" height="170"></canvas></div>
 <div class="panel"><h2>Minibatches / second</h2><canvas id="mbs" width="440" height="170"></canvas></div>
</div>
<script>
__COMMON__
async function liveRow() {
  try { return await (await fetch('api/system-now')).json(); }
  catch (e) { return null; }
}
function series(field) {
  return reports.filter(r => r[field] != null).map(r => [r.iteration, r[field]]);
}
let lastLive = null;
async function render(fresh) {
  document.getElementById('meta').textContent =
    `${cur} · ${curInfo.modelClass || '?'} · ${reports.length} reports`;
  const live = await liveRow();
  const rows = [
    ['backend', curInfo.backend], ['device count', curInfo.deviceCount],
    ['model', curInfo.modelClass], ['parameters', curInfo.numParams],
  ];
  if (live) {
    rows.push(['host RSS now (MB)', live.hostRssMb &&
               live.hostRssMb.toFixed(1)]);
    (live.devices || []).forEach((d, i) => rows.push(
      [`device ${i} (${d.kind})`, d.bytesInUse == null ? 'n/a' :
       `${(d.bytesInUse / 1e6).toFixed(1)} MB` +
       (d.bytesLimit ? ` / ${(d.bytesLimit / 1e6).toFixed(0)} MB` : '')]));
  }
  const tbl = document.getElementById('static');
  tbl.replaceChildren(...rows.map(([k, v]) => {
    const tr = document.createElement('tr');
    const td1 = document.createElement('td'); td1.textContent = k;
    const td2 = document.createElement('td'); td2.textContent = v ?? '?';
    tr.appendChild(td1); tr.appendChild(td2);
    return tr;
  }));
  if (!fresh) return;
  drawLines('rss', {rss: series('memoryRssMb')});
  drawLines('dev', {dev: series('deviceMemMb')});
  drawLines('dur', {ms: series('durationMs')});
  drawLines('mbs', {mbs: series('minibatchesPerSecond')});
}
</script></body></html>""".replace("__COMMON__", _COMMON_JS) \
    .replace("__STYLE__", _STYLE).replace("__NAV__", _nav("sy"))


def _host_rss_mb() -> dict:
    """Current and peak host RSS. getrusage only exposes the lifetime PEAK
    (ru_maxrss); current usage comes from /proc/self/statm so the system tab
    can show memory actually going down after a spike."""
    import resource
    import sys

    # ru_maxrss is KiB on Linux but BYTES on macOS
    div = 1024.0 * 1024.0 if sys.platform == "darwin" else 1024.0
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / div
    cur = None
    try:
        with open("/proc/self/statm") as f:
            cur = int(f.read().split()[1]) * (resource.getpagesize() / 1e6)
    except (OSError, ValueError, IndexError):
        pass  # non-Linux: only the peak is available
    return {"hostRssMb": cur if cur is not None else peak,
            "hostPeakRssMb": peak}


def _jax_initialized() -> bool:
    """True only if a JAX backend already exists in THIS process. The UI
    server may run standalone (remote-router deployment); calling
    jax.local_devices() there would force-initialize XLA — grabbing the TPU
    lock / preallocating GPU memory out from under the actual trainer."""
    import sys

    jx = sys.modules.get("jax")
    if jx is None:
        return False
    # No public call answers this without side effects on JAX 0.9.0:
    # jax.devices(), jax.default_backend() and jax.extend.backend.backends()
    # all initialize the backends they report on. So this reads the one
    # private predicate, and a JAX that moves it reads as "not initialized"
    # (the system tab then shows host memory only).
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge.backends_are_initialized())
    except (ImportError, AttributeError):
        return False


def _system_now() -> dict:
    """Live host + device memory snapshot (system tab; ref: the train UI's
    system page showing JVM/off-heap/GPU memory)."""
    out = dict(_host_rss_mb())
    out["devices"] = []
    if not _jax_initialized():
        return out
    import jax

    try:
        for d in jax.local_devices():
            stats = {}
            try:
                stats = d.memory_stats() or {}
            except Exception:
                pass
            out["devices"].append({
                "kind": getattr(d, "device_kind", str(d)),
                "bytesInUse": stats.get("bytes_in_use"),
                "bytesLimit": stats.get("bytes_limit"),
            })
    except Exception:
        pass
    return out


class _Handler(BaseHTTPRequestHandler):
    server_version = "dl4jtpu-ui/1.0"

    def log_message(self, *a):  # silence per-request stderr spam
        pass

    def _json(self, obj, code=200):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _storages(self) -> List[StatsStorage]:
        return self.server.ui._storages  # type: ignore[attr-defined]

    def _metrics_rollup(self, key: str) -> List[dict]:
        """Latest ServingMetrics sub-payload ``key`` per serving worker
        (the shared shape of /api/slo and /api/qos): walk every attached
        storage's sessions/workers, pick the newest ServingMetrics
        update carrying ``key``, and ride ``rejections_by_reason``
        alongside for taxonomy cross-checking."""
        out = []
        for st in self._storages():
            for sid in st.listSessionIDs():
                for worker in st.listWorkerIDsForSession(sid) or []:
                    ups = st.getUpdates(sid, "ServingMetrics", worker)
                    if not ups:
                        continue
                    latest = ups[-1]
                    if isinstance(latest, dict) and key in latest:
                        out.append({
                            "sessionId": sid, "workerId": worker,
                            key: latest[key],
                            "rejections_by_reason":
                                latest.get("rejections_by_reason"),
                        })
        return out

    def _html(self, page: str):
        body = page.encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/html; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        url = urlparse(self.path)
        parts = [p for p in url.path.split("/") if p]
        if not parts:
            self._html(_PAGE)
            return
        if parts == ["model"]:
            self._html(_MODEL_PAGE)
            return
        if parts == ["system"]:
            self._html(_SYSTEM_PAGE)
            return
        if parts == ["api", "system-now"]:
            self._json(_system_now())
            return
        if parts == ["api", "sessions"]:
            out = []
            for st in self._storages():
                for sid in st.listSessionIDs():
                    workers = st.listWorkerIDsForSession(sid) or ["worker_0"]
                    out.append({
                        "sessionId": sid, "workers": workers,
                        "info": st.getStaticInfo(sid, "StatsListener", workers[0]),
                    })
            self._json(out)
            return
        if parts == ["api", "slo"]:
            # rolling-window SLO roll-up per serving worker: p50/p95/p99
            # over the in-window successes + reason-bucketed error rate
            # (serving.metrics.SlidingWindowStats — NOT lifetime
            # histograms). Reasons use the same taxonomy as
            # rejections_by_reason.
            self._json(self._metrics_rollup("slo"))
            return
        if parts == ["api", "qos"]:
            # multi-tenant QoS roll-up per serving worker (serving/qos.py):
            # per-tenant served/shed + reason breakdown, queue-wait
            # histograms by priority class, quota/SLO-shed/retry-budget
            # counters and whether the burn governor is currently
            # shedding. rejections_by_reason cross-check convention:
            # admission-path reasons (quota_exceeded, slo_shed,
            # queue_full, deadline, ...) match the per-tenant sums
            # exactly; incident-style reasons (poisoned,
            # retry_budget_exhausted, watchdog) count once per INCIDENT
            # engine-wide but once per victim request per tenant, the
            # same convention rejections_by_reason has used for
            # 'poisoned' since PR 5.
            self._json(self._metrics_rollup("qos"))
            return
        if parts == ["api", "serving", "spec"]:
            # speculative-decoding roll-up per serving worker
            # (serving/generation.py speculative=SpecConfig): fleet
            # acceptance rate (spec_tokens_accepted / proposed), the
            # fallbacks counter (turns degraded to plain decode — a
            # dead draft NEVER sheds, so this is its only footprint),
            # and per-tenant proposed/accepted/acceptance_rate on the
            # same bounded-cardinality label scheme as /api/qos.
            self._json(self._metrics_rollup("spec"))
            return
        if parts == ["api", "cluster"]:
            # pod-slice control-plane view (serving/cluster.py): one
            # entry per live ClusterDirectory in this process — per-host
            # slots/blocks/breaker/SLO + drain state + heartbeat age,
            # the fleet roll-up (alive/draining/quorum/degraded, summed
            # capacity), each front door's routed/shed/hedge mix, and —
            # when an ElasticityLoop watches the directory — its latest
            # join/drain decision (the loop itself may be feeding off
            # THIS endpoint via http_snapshot_source; the decision block
            # is additive, so the payload stays a valid planner input)
            from deeplearning4j_tpu.serving.cluster import (
                all_directories, all_elasticity_loops,
            )
            loops = {id(lp.directory): lp for lp in all_elasticity_loops()}
            payload = []
            for d in all_directories():
                snap = d.api_snapshot()
                lp = loops.get(id(d))
                if lp is not None and lp.planner.last_decision is not None:
                    snap["elasticity"] = lp.planner.last_decision
                payload.append(snap)
            self._json(payload)
            return
        if parts == ["api", "timeseries"]:
            # fleet time-series telemetry (serving/timeseries.py, fed at
            # heartbeat cadence through HostStatus.sample): one entry
            # per live ClusterDirectory carrying a fleet-side
            # TimeSeriesStore — per-host sample rings plus the fitted
            # cost models the elasticity planner's decisions cite.
            # ?limit=N bounds samples per host (default 100);
            # directories without a store are skipped (timeseries=None
            # is the bitwise-inert default).
            from deeplearning4j_tpu.serving.cluster import all_directories
            from deeplearning4j_tpu.serving.timeseries import (
                cheapest_cell, fit_cost_models,
            )
            q = parse_qs(url.query)
            limit = max(1, min(int(q.get("limit", ["100"])[0]), 1000))
            payload = []
            for d in all_directories():
                ts = getattr(d, "timeseries", None)
                if ts is None:
                    continue
                snap = ts.api_snapshot(limit=limit)
                models = fit_cost_models(ts)
                snap["cost_models"] = models
                snap["cheapest_cell"] = cheapest_cell(models)
                payload.append(snap)
            self._json(payload)
            return
        if parts == ["api", "traces"]:
            # finished request traces retained by every Tracer in this
            # process (serving/tracing.py tail sampling: errors always,
            # successes at sample_rate). ?limit=N (default 50) bounds the
            # payload, ?engine= filters by engine name.
            from deeplearning4j_tpu.serving.tracing import all_tracers
            q = parse_qs(url.query)
            # clamp: limit<=0 would turn the [-limit:] slices into "all"
            limit = max(1, min(int(q.get("limit", ["50"])[0]), 1000))
            engine = q.get("engine", [None])[0]
            traces, tracers, total = [], [], 0
            for t in all_tracers():
                # per-tracer limit before the merge: the newest N per
                # tracer is a superset of the global newest N, and it
                # avoids serializing hundreds of full event lists per poll
                matching = t.traces(engine=engine)
                total += len(matching)
                traces.extend(tr.to_dict() for tr in matching[-limit:])
                tracers.append(t.stats())
            traces.sort(key=lambda d: d["start"])
            self._json({"count": total, "traces": traces[-limit:],
                        "tracers": tracers})
            return
        if parts == ["api", "serving"]:
            # serving-engine metric snapshots (typeId ServingMetrics —
            # published by serving.metrics.ServingMetrics.publish through
            # the same storage SPI as training stats). Generation engines
            # publish through the same snapshot; their headline decode
            # signals are lifted into a "generation" roll-up so dashboards
            # need not dig through the full snapshot.
            out = []
            for st in self._storages():
                for sid in st.listSessionIDs():
                    for worker in st.listWorkerIDsForSession(sid) or []:
                        ups = st.getUpdates(sid, "ServingMetrics", worker)
                        if not ups:
                            continue
                        entry = {"sessionId": sid, "workerId": worker,
                                 "reports": len(ups), "latest": ups[-1]}
                        latest = ups[-1]
                        # gate on prefills (not decode steps): an engine
                        # serving max_new_tokens=1 retires every stream at
                        # prefill and never runs a decode iteration — and
                        # prefix prefills count (a pure shared-prefix
                        # workload performs no per-stream prefill at all)
                        if isinstance(latest, dict) \
                                and (latest.get("prefills_total")
                                     or latest.get("prefix_prefills_total")):
                            entry["generation"] = {
                                k: latest.get(k) for k in (
                                    "decode_tokens_per_sec", "slot_occupancy",
                                    "generated_tokens_total",
                                    "generations_completed", "ttft_ms",
                                    "prefill_ms", "decode_step_ms",
                                    "kv_blocks_total", "kv_blocks_in_use",
                                    "kv_blocks_pinned", "kv_block_occupancy",
                                    "kv_fragmentation",
                                    "prefix_prefills_total",
                                    "prefix_hits_total",
                                    "kv_cow_copies_total")}
                        # resilience roll-up (PR 3): retry/breaker/watchdog/
                        # fallback counters + shedding causes, so "why is
                        # this engine degraded" is one GET. Gated on the
                        # new-format key so pre-PR-3 snapshots still render.
                        if isinstance(latest, dict) \
                                and "retries_total" in latest:
                            entry["resilience"] = {
                                k: latest.get(k) for k in (
                                    "retries_total", "watchdog_restarts",
                                    "fallback_serves",
                                    "rejected_circuit_open",
                                    "breaker_opened_total",
                                    "breaker_half_open_total",
                                    "breaker_closed_total",
                                    "faults_injected_total",
                                    "rejections_by_reason")}
                        out.append(entry)
            self._json(out)
            return
        if len(parts) == 4 and parts[:2] == ["api", "updates"]:
            sid, worker = parts[2], parts[3]
            start = int(parse_qs(url.query).get("from", ["0"])[0])
            updates: List[dict] = []
            for st in self._storages():
                updates = st.getUpdates(sid, "StatsListener", worker)
                if updates:
                    break
            self._json(updates[start:])
            return
        self._json({"error": "not found"}, 404)

    def do_POST(self):
        if urlparse(self.path).path != "/remote/receive":
            self._json({"error": "not found"}, 404)
            return
        n = int(self.headers.get("Content-Length", "0"))
        try:
            msg = json.loads(self.rfile.read(n).decode())
            target = self.server.ui._remote_target()  # type: ignore[attr-defined]
            if msg.get("kind") == "static":
                target.putStaticInfo(msg["sessionId"], msg["typeId"],
                                     msg["workerId"], msg["info"])
            else:
                target.putUpdate(msg["sessionId"], msg["typeId"],
                                 msg["workerId"], msg["report"])
            self._json({"ok": True})
        except (KeyError, ValueError, TypeError, AttributeError,
                json.JSONDecodeError) as e:  # malformed body → 400, not a dead thread
            self._json({"ok": False, "error": str(e)}, 400)


class UIServer:
    """Embedded dashboard (ref: UIServer.getInstance() — same lifecycle:
    process-wide singleton, attach any number of storages, stop() to halt)."""

    _instance: Optional["UIServer"] = None
    _lock = threading.Lock()

    def __init__(self, port: int = 0):
        self._storages: List[StatsStorage] = []
        self._remote_storage: Optional[StatsStorage] = None
        self._remote_lock = threading.Lock()
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
        self._httpd.ui = self  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.1},
            daemon=True, name="dl4jtpu-ui-server")
        self._thread.start()

    @classmethod
    def getInstance(cls, port: int = 9000) -> "UIServer":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls(port)
        return cls._instance

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}/"

    def attach(self, storage: StatsStorage):
        if storage not in self._storages:
            self._storages.append(storage)

    def detach(self, storage: StatsStorage):
        if storage in self._storages:
            self._storages.remove(storage)

    def _remote_target(self) -> StatsStorage:
        """Storage that /remote/receive lands in: the first attached storage,
        lazily creating (and attaching) an in-memory one if none. Locked —
        each POST runs on its own ThreadingHTTPServer thread, and two first
        posts racing here must not each create a storage."""
        with self._remote_lock:
            if self._storages:
                return self._storages[0]
            if self._remote_storage is None:
                self._remote_storage = InMemoryStatsStorage()
                self.attach(self._remote_storage)
            return self._remote_storage

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)
        with UIServer._lock:
            if UIServer._instance is self:
                UIServer._instance = None


class RemoteStatsStorageRouter(StatsStorage):
    """Write-side router posting reports to a UIServer over HTTP (ref:
    RemoteUIStatsStorageRouter). Only the router (write) half of the SPI is
    live; reads raise — exactly the reference's split.

    Telemetry must never kill training: network failures are retried
    ``retries`` times with a short backoff, then the report is DROPPED with a
    one-time warning (the reference queues and retries asynchronously; a
    drop-after-retry keeps the same "fit() survives a UI outage" contract
    without a background thread).

    ``queue_capacity > 0`` adds the reference's asynchronous mode: reports
    enqueue into a BOUNDED queue drained by one background sender thread
    (same retry-then-drop delivery per report), so the posting thread
    never blocks on the network at all — the mode the serving cluster's
    heartbeat/trace-aggregation path (serving/cluster.py HttpTransport)
    rides. On overflow the NEWEST report is dropped and counted
    (``dropped`` / ``dropped_overflow``): heartbeats and metrics are
    freshness-dated, so a backlog older than the queue is worth more than
    the report that found it full. ``flush()`` drains for tests/shutdown."""

    def __init__(self, url: str, timeout: float = 5.0, retries: int = 2,
                 retry_delay: float = 0.2, queue_capacity: int = 0):
        self.url = url.rstrip("/") + "/remote/receive"
        self.timeout = timeout
        self.retries = retries
        self.retry_delay = retry_delay
        self.dropped = 0
        self.dropped_overflow = 0
        self.delivered = 0
        self._warned = False
        if queue_capacity < 0:
            raise ValueError("queue_capacity must be >= 0 (0 = synchronous)")
        self.queue_capacity = queue_capacity
        self._q: Optional[list] = None
        if queue_capacity > 0:
            self._q = []
            self._q_cv = threading.Condition()
            self._sending = False
            self._closed = False
            self._sender = threading.Thread(
                target=self._drain, daemon=True,
                name="remote-stats-router-sender")
            self._sender.start()

    def _post(self, payload: dict):
        data = json.dumps(payload).encode()
        for attempt in range(self.retries + 1):
            try:
                req = urllib.request.Request(
                    self.url, data=data,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=self.timeout) as resp:
                    return json.loads(resp.read().decode())
            except (urllib.error.URLError, OSError, TimeoutError) as e:
                if attempt < self.retries:
                    time.sleep(self.retry_delay)
                    continue
                self.dropped += 1
                if not self._warned:
                    self._warned = True
                    warnings.warn(
                        f"RemoteStatsStorageRouter: dropping stats reports, "
                        f"UI server at {self.url} unreachable ({e})")
                return None

    # ------------------------------------------------------- async queue
    def _enqueue(self, payload: dict):
        with self._q_cv:
            if self._closed:
                # post-close submissions are dropped but COUNTED: every
                # report is either delivered or accounted for in
                # ``dropped`` — the invariant dashboards reconcile on
                self.dropped += 1
                return
            if len(self._q) >= self.queue_capacity:
                # drop-on-overflow, NEWEST report: the queued backlog is
                # older and its delivery order matters to pollers; both
                # counters move so dashboards separate "network down"
                # (dropped only) from "queue undersized" (overflow too)
                self.dropped += 1
                self.dropped_overflow += 1
                if not self._warned:
                    self._warned = True
                    warnings.warn(
                        f"RemoteStatsStorageRouter: bounded queue "
                        f"(capacity {self.queue_capacity}) overflowed; "
                        f"dropping reports")
                return
            self._q.append(payload)
            self._q_cv.notify()

    def _drain(self):
        while True:
            with self._q_cv:
                while not self._q and not self._closed:
                    self._q_cv.wait()
                if self._closed and not self._q:
                    return
                payload = self._q.pop(0)
                self._sending = True
            try:
                if self._post(payload) is not None:
                    self.delivered += 1
            finally:
                with self._q_cv:
                    self._sending = False
                    self._q_cv.notify_all()

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until the bounded queue is drained (async mode only;
        a no-op synchronously). True when fully drained in time."""
        if self._q is None:
            return True
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._q_cv:
            while self._q or self._sending:
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                self._q_cv.wait(remaining)
        return True

    def close(self, timeout: float = 5.0):
        """Stop the sender after draining what it can (async mode)."""
        if self._q is None:
            return
        self.flush(timeout=timeout)
        with self._q_cv:
            self._closed = True
            self._q_cv.notify_all()
        self._sender.join(timeout=2.0)

    def putUpdate(self, sessionId, typeId, workerId, report):
        payload = {"kind": "update", "sessionId": sessionId,
                   "typeId": typeId, "workerId": workerId, "report": report}
        if self._q is not None:
            self._enqueue(payload)
        else:
            self._post(payload)

    def putStaticInfo(self, sessionId, typeId, workerId, info):
        payload = {"kind": "static", "sessionId": sessionId,
                   "typeId": typeId, "workerId": workerId, "info": info}
        if self._q is not None:
            self._enqueue(payload)
        else:
            self._post(payload)
