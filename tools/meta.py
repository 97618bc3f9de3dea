"""Print a url's metadata: for a document, its actor list, clock, and
history length; for a hyperfile, its size and mime type (reference
tools/Meta.ts — `repo.meta(url, cb)` surfaced on the command line).

    python tools/meta.py /path/to/repo 'hypermerge:/<docId>'
    python tools/meta.py /path/to/repo 'hyperfile:/<fileId>'
    python tools/meta.py --devices
    python tools/meta.py /path/to/repo --stats
    python tools/meta.py --dht [--bootstrap host:port,host:port]

Output is one JSON object. Documents are opened first (metadata queries
answer from the open doc's backend state); unknown urls print null and
exit non-zero.

`--devices` prints the visible-device topology instead (no repo
needed): device count, platform/kind, default backend and process
count — the same object the Telemetry reply carries as `device`.

`--dht` probes a running DHT fleet from outside: boots an EPHEMERAL
node (net/discovery/dht.py), bootstraps it from `--bootstrap` or
`HM_DHT_BOOTSTRAP`, walks toward its own id, and prints the node id
and per-bucket occupancy JSON — "is the fleet reachable and how big
does it look from here" in one command. `nodes` is the routing-table
size after the walk; an empty table means no bootstrap answered.

`--stats` opens the repo (and its docs) and prints the process-wide
telemetry snapshot JSON — the registry every subsystem now reports
into (hypermerge_tpu/telemetry/) instead of the per-object stats
dicts it replaced. Same counter names as tools/top.py.
"""

import argparse
import json
import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from hypermerge_tpu.repo import Repo  # noqa: E402
from hypermerge_tpu.utils.ids import is_doc_url  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("repo", nargs="?", help="repo directory")
    ap.add_argument(
        "url", nargs="?",
        help="hypermerge:/ doc url or hyperfile:/ url",
    )
    ap.add_argument(
        "--timeout", type=float, default=30.0,
        help="seconds to wait for the doc to come up (default 30)",
    )
    ap.add_argument(
        "--devices", action="store_true",
        help="print visible device / mesh topology JSON and exit",
    )
    ap.add_argument(
        "--stats", action="store_true",
        help="open the repo and print the telemetry registry snapshot",
    )
    ap.add_argument(
        "--dht", action="store_true",
        help="probe the DHT fleet with an ephemeral node and print "
        "node id + bucket occupancy JSON",
    )
    ap.add_argument(
        "--bootstrap", default=None,
        help="host:port[,host:port] DHT bootstrap list for --dht "
        "(default: HM_DHT_BOOTSTRAP)",
    )
    args = ap.parse_args()

    if args.dht:
        from hypermerge_tpu.net.discovery import DhtNode

        bootstrap = None
        if args.bootstrap:
            bootstrap = []
            for part in args.bootstrap.split(","):
                host, _, port = part.strip().rpartition(":")
                bootstrap.append((host, int(port)))
        node = DhtNode(bootstrap=bootstrap)
        try:
            node.bootstrap_now()
            print(json.dumps({
                "node_id": node.id_hex,
                "dht_address": list(node.address),
                "nodes": node.table.size(),
                "buckets": node.table.occupancy(),
                "records": node.records.size(),
            }, sort_keys=True), flush=True)
            sys.exit(0 if node.table.size() else 1)
        finally:
            node.close()
    if args.devices:
        from hypermerge_tpu.parallel.mesh import device_topology

        print(json.dumps(device_topology(), sort_keys=True), flush=True)
        return
    if args.stats:
        if args.repo is None:
            ap.error("--stats requires a repo directory")
        from hypermerge_tpu import telemetry

        payload = telemetry.snapshot_repo(args.repo)
        print(
            json.dumps(payload["counters"], sort_keys=True), flush=True
        )
        return
    if args.repo is None or args.url is None:
        ap.error("repo and url are required (or use --devices)")

    repo = Repo(path=args.repo)
    try:
        if is_doc_url(args.url):
            # metadata answers from the open doc: materialize it first
            try:
                repo.open(args.url).value(timeout=args.timeout)
            except TimeoutError:
                # unknown doc (nothing local, no peer): same contract
                # as an unknown hyperfile — null, non-zero exit
                print("null", flush=True)
                sys.exit(1)
        got = {}
        done = threading.Event()

        def on_meta(payload) -> None:
            got["meta"] = payload
            done.set()

        repo.meta(args.url, on_meta)
        if not done.wait(args.timeout):
            print("timed out waiting for metadata", file=sys.stderr)
            sys.exit(2)
        meta = got["meta"]
        print(json.dumps(meta, default=str, sort_keys=True), flush=True)
        if meta is None:
            sys.exit(1)
    finally:
        repo.close()


if __name__ == "__main__":
    main()
