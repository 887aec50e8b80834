"""Merge a multihost run's per-host telemetry streams into ONE
Perfetto-loadable Chrome trace.

Every host of a `tpu_telemetry=trace` run streams its spans/events as
``events-host<k>.jsonl`` under the shared ``tpu_trace_dir`` (the
incremental JSONL survives a host dying mid-run — exactly the runs
worth reading).  Rank 0 (or any machine that can see the shared
directory) merges them:

    python tools/trace_merge.py <tpu_trace_dir> [-o merged.json]

Each host becomes one Perfetto process row (pid = host index, named
``lightgbm_tpu host k``); span nesting/threads are preserved per host.
Host clocks are independent monotonic origins, so rows are aligned per
host, not globally — good enough to see which host stalled in which
collective, which is the question multihost traces exist to answer.
Malformed trailing lines (a host died mid-write) are skipped with a
count, never an error.
"""

import argparse
import glob
import json
import os
import re
import sys


def merge(trace_dir: str):
    """-> (chrome_trace_dict, per_host_line_counts, skipped_lines)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "events-host*.jsonl")))
    if not paths:
        raise FileNotFoundError(
            f"no events-host*.jsonl under {trace_dir!r} — was the run "
            "launched with tpu_telemetry=trace and tpu_trace_dir set?")
    events = []
    counts = {}
    skipped = 0
    for path in paths:
        m = re.search(r"events-host(\d+)\.jsonl$", path)
        host = int(m.group(1)) if m else 0
        events.append({"name": "process_name", "ph": "M", "pid": host,
                       "tid": 0, "args": {"name": f"lightgbm_tpu host {host}"}})
        n = 0
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    skipped += 1  # torn tail of a dying host
                    continue
                rec = {"name": ev.get("name", "?"),
                       "ph": "X" if ev.get("kind") == "span" else "i",
                       "ts": float(ev.get("ts_us", 0.0)),
                       "pid": int(ev.get("host", host)),
                       "tid": int(ev.get("tid", 0)),
                       "args": dict(ev.get("tags") or {})}
                for key in ("id", "parent_id"):  # absent in old streams
                    if key in ev:
                        rec["args"][key] = ev[key]
                if rec["ph"] == "X":
                    rec["dur"] = float(ev.get("dur_us", 0.0))
                else:
                    rec["s"] = "t"
                events.append(rec)
                n += 1
        counts[host] = n
    return ({"traceEvents": events, "displayTimeUnit": "ms"},
            counts, skipped)


def merge_blackbox(trace_dir: str):
    """Overlay multiple hosts' ``blackbox-host<k>.json`` flight-recorder
    dumps (ISSUE 12) into one wall-clock timeline and answer "who hung
    first".

    Unlike the JSONL span streams (per-host monotonic origins), blackbox
    entries carry epoch seconds — directly comparable across hosts — so
    the overlay can order the LAST thing each host did globally.  The
    hang verdict: for each host, the newest ``span_begin`` with no later
    matching ``span_end`` is its in-flight site; the host whose
    in-flight site has the EARLIEST wall time hung first (its peers'
    later in-flight collectives are them waiting on it).

    -> (overlay dict, per-host verdicts, text report lines)
    """
    paths = sorted(glob.glob(os.path.join(trace_dir,
                                          "blackbox-host*.json")))
    if not paths:
        raise FileNotFoundError(
            f"no blackbox-host*.json under {trace_dir!r} — blackbox "
            "dumps land in tpu_obs_blackbox_dir / "
            "LIGHTGBM_TPU_BLACKBOX_DIR (default: the working directory)")
    hosts = {}
    timeline = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        host = int(rec.get("host", 0))
        entries = rec.get("entries", [])
        for e in entries:
            timeline.append({**e, "host": host})
        # in-flight: newest span_begin whose (name, tid) never ended
        in_flight = None
        ended = set()
        for e in reversed(entries):
            key = (e.get("name"), e.get("tid"))
            if e.get("kind") == "span_end":
                ended.add(key)
            elif e.get("kind") == "span_begin" and key not in ended:
                in_flight = e
                break
        hosts[host] = {"reason": rec.get("reason"),
                       "dump_t": rec.get("t"),
                       "entries": len(entries),
                       "in_flight": in_flight}
    timeline.sort(key=lambda e: e.get("t", 0.0))
    report = []
    # dumps overwrite in place per host, so a shared dir can hold a
    # STALE file from an earlier run; a wide dump-time spread means the
    # verdict below may be comparing different deaths
    dump_ts = [v["dump_t"] for v in hosts.values()
               if isinstance(v.get("dump_t"), (int, float))]
    if len(dump_ts) > 1 and max(dump_ts) - min(dump_ts) > 300.0:
        report.append(
            f"warning: host dump times differ by "
            f"{max(dump_ts) - min(dump_ts):.0f}s — a dump may be stale "
            "from an earlier run; treat the verdict accordingly")
    stuck = [(h, v["in_flight"]) for h, v in sorted(hosts.items())
             if v["in_flight"] is not None]
    for h, v in sorted(hosts.items()):
        flight = v["in_flight"]
        site = flight["name"] if flight else "(none in flight)"
        report.append(f"host {h}: dumped '{v['reason']}' with "
                      f"{v['entries']} entries; in flight: {site}")
    if stuck:
        first = min(stuck, key=lambda hv: hv[1].get("t", 0.0))
        report.append(
            f"verdict: host {first[0]} hung first — entered "
            f"{first[1]['name']!r} at t={first[1].get('t', 0.0):.3f} "
            "and never left; later in-flight sites on other hosts are "
            "peers waiting on it")
    else:
        report.append("verdict: no in-flight collective in any dump "
                      "(the deaths were not hangs)")
    return ({"hosts": hosts, "timeline": timeline}, hosts, report)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir", help="the run's tpu_trace_dir (or, with "
                                      "--blackbox, the blackbox dump dir)")
    ap.add_argument("-o", "--out", default=None,
                    help="output path (default: <trace_dir>/merged.json)")
    ap.add_argument("--blackbox", action="store_true",
                    help="overlay blackbox-host*.json flight-recorder "
                         "dumps instead of JSONL span streams and print "
                         "the who-hung-first verdict")
    args = ap.parse_args(argv)
    if args.blackbox:
        out = args.out or os.path.join(args.trace_dir,
                                       "merged-blackbox.json")
        overlay, hosts, report = merge_blackbox(args.trace_dir)
        with open(out, "w") as f:
            json.dump(overlay, f)
        for line in report:
            print(line)
        print(f"overlaid {len(hosts)} host dump(s) -> {out}")
        return out
    out = args.out or os.path.join(args.trace_dir, "merged.json")
    trace, counts, skipped = merge(args.trace_dir)
    with open(out, "w") as f:
        json.dump(trace, f)
    hosts = ", ".join(f"host{k}: {n}" for k, n in sorted(counts.items()))
    print(f"merged {sum(counts.values())} events ({hosts}) -> {out}")
    if skipped:
        print(f"skipped {skipped} malformed line(s) (torn host tails)",
              file=sys.stderr)
    return out


if __name__ == "__main__":
    main()
