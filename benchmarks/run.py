"""`python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`: one run of one cell on the chip this machine holds.

The last line of stdout is the result object and nothing else goes
into it; a run that finds no chip, or whose children fail, or whose
files name a check, key law, role, reduction or kernel that nothing
provides, exits non-zero and prints no such line. Each number the
check compared stands beside its limit under the result's last key,
`checks`, and on the last lines of stderr."""

import time

T_PROCESS = time.monotonic()      # set-up is timed from process start

import argparse                   # noqa: E402
import json                       # noqa: E402
import signal                     # noqa: E402
import sys                        # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    from . import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS)
    except harness.RunFailed as e:
        print(f"run failed: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sys.exit(main())
