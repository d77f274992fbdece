"""Pull an on-demand device profile through MonitorServer `/profile`
while a real workload runs (ISSUE 12).

Boots the process-wide monitor endpoint, runs one bench.py ladder config
in a background thread (so the device is actually busy during the
capture window), GETs `/profile?secs=N` mid-run, and writes the returned
zip (perfetto/tensorboard-loadable xplane protos) to --out.  Exercises
the exact path a fleet aggregator uses against a slow replica: no
restart, no code change, one HTTP GET.

    python scripts/profile_capture.py --config gpt124m_decode --secs 5
    python scripts/profile_capture.py --config resnet50 --secs 5

Runnable on CPU (smoke) and on chip.  Exit 0 with a saved artifact,
exit 3 when this backend's profiler is unavailable (the endpoint's clean 501) — an outage, not a bug.
"""
import argparse
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt124m_decode",
                    help="bench.py ladder config to run under the probe")
    ap.add_argument("--secs", type=float, default=5.0,
                    help="capture window seconds")
    ap.add_argument("--warmup", type=float, default=2.0,
                    help="seconds to let the workload compile/warm "
                         "before capturing")
    ap.add_argument("--out", default="/tmp/ptpu_profiles",
                    help="directory the zip artifact lands in")
    args = ap.parse_args()

    import bench
    from paddle_tpu import monitor

    if args.config not in bench.LADDER:
        sys.exit(f"unknown config {args.config!r}; one of "
                 f"{sorted(bench.LADDER)}")
    srv = monitor.start_server(0)
    print(f"monitor endpoint: {srv.url}")

    errs = []

    def work():
        try:
            bench.LADDER[args.config]()
        except Exception as e:   # the capture still stands; report it
            errs.append(e)

    t = threading.Thread(target=work, name="profile-workload",
                         daemon=True)
    t.start()
    time.sleep(args.warmup)

    url = f"{srv.url}/profile?secs={args.secs}"
    print(f"GET {url} ...")
    try:
        body = urllib.request.urlopen(
            url, timeout=args.secs + 120).read()
    except urllib.error.HTTPError as e:
        detail = e.read().decode(errors="replace")[:300]
        if e.code == 501:
            print(f"profiler unavailable on this backend (501): "
                  f"{detail}", file=sys.stderr)
            sys.exit(3)
        sys.exit(f"/profile failed: {e.code} {detail}")

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(
        args.out, f"profile_{args.config}_{os.getpid()}.zip")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(body)
    os.replace(tmp, path)
    print(f"saved {len(body)} bytes -> {path}")

    t.join(timeout=600)
    if errs:
        print(f"workload error (capture still saved): {errs[0]!r}",
              file=sys.stderr)
    import zipfile

    with zipfile.ZipFile(path) as z:
        names = z.namelist()
    assert names, "empty profile artifact"
    print(f"artifact OK: {len(names)} files, e.g. {names[0]}")


if __name__ == "__main__":
    main()
