"""One run of a benchmark cell from a checkout, with the model's digest.

  python3 scripts/cell_digest.py --root <checkout> --tag <name> -- \
      --workload <cell> --seed <n> --seconds 36 --trace 0

Runs ``<checkout>/benchmark/run.py`` in this process with ``lgb.train``
wrapped: after the job, ``dump_model()``'s trees are hashed and written
with the run's arguments to ``chiprun_out/cell_digest/<tag>.json`` (of
the directory the command was started in). Two checkouts that print the
same digest for one seed grew the same model. The benchmark's own result
line goes to standard output as always; nothing inside the measured
window changes (the hash is taken after ``lgb.train`` returns).
"""
import argparse
import hashlib
import json
import os
import runpy
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    out_dir = os.path.join(os.getcwd(), "chiprun_out", "cell_digest")
    os.makedirs(out_dir, exist_ok=True)
    root = os.path.abspath(args.root)
    bench = os.path.join(root, "benchmark")
    sys.path[:0] = [bench, root]
    import lightgbm_tpu as lgb
    assert os.path.dirname(os.path.dirname(lgb.__file__)) == root
    train = lgb.train

    def digesting(*a, **kw):
        bst = train(*a, **kw)
        trees = bst.dump_model(num_iteration=-1)["tree_info"]
        text = json.dumps(trees, sort_keys=True)
        counters = bst.telemetry().get("counters", {})
        with open(os.path.join(out_dir, args.tag + ".json"), "w") as fh:
            json.dump({"tag": args.tag, "root": root, "args": rest,
                       "trees": len(trees),
                       "model_sha256": hashlib.sha256(
                           text.encode()).hexdigest(),
                       "route_counters": {k: v for k, v in counters.items()
                                          if k.startswith("route.")}}, fh)
        return bst
    lgb.train = digesting
    os.chdir(root)
    sys.argv = [os.path.join(bench, "run.py")] + rest
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
