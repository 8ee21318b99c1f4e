"""One fleet worker that owns the chip, behind a front process that stays off it.

    python benchmarks/fleet_chip_check.py      # on a TPU host, ~2 min

A chip belongs to one process at a time. ``FleetRouter`` spawns its workers
as processes, so the front must never initialise a JAX backend on the
accelerator: this script pins ITS OWN JAX to the host CPU (a config update,
not an environment variable, so the worker does not inherit it), builds a
BERT-base-width causal decoder (depth cut to 2 layers; the full depth is
``chip_smoke.py``'s job) into a ModelSerializer archive there, and boots
``FleetRouter(n_workers=1)``. The worker inherits the environment as it
stands, opens the TPU, warms up and answers one generate request through the
front. The worker's device gauges, fanned into the front's ``/metrics``, are
the proof of which platform it ran on.

With more workers than chips the second worker cannot open the device
(docs/SERVING.md#fleet); pinning workers to chips is ROADMAP Reach 7.

Exits non-zero unless the worker ran on a TPU. Every process it starts is
stopped on the way out.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL_ID = "bert-base-width-decoder"


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")   # the front never opens the chip
    import numpy as np

    from deeplearning4j_tpu.serving.fleet import FleetRouter, fleet_spec
    from deeplearning4j_tpu.util.model_serializer import ModelSerializer
    from deeplearning4j_tpu.zoo.bert import Bert

    net = Bert.base(causal=True, task="mlm", max_length=1024,
                    hidden_dropout=0.0, n_layers=2).init()
    front = jax.devices()[0].platform
    print(f"fleet_chip_check: front process on platform={front}", flush=True)
    if front != "cpu":
        print("front process must stay on the host CPU", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory(prefix="fleet-chip-check-") as tmp:
        path = os.path.join(tmp, "decoder.zip")
        ModelSerializer.write_model(net, path, save_updater=False)
        spec = fleet_spec(models=[{
            "id": MODEL_ID, "path": path, "kind": "generate",
            "model_kw": {"bucketing": {"batch_buckets": [1],
                                       "seq_buckets": [64]}},
            "register": {"max_wait_ms": 2.0}}])
        t0 = time.perf_counter()
        fleet = FleetRouter(spec, n_workers=1, name="chip-check",
                            boot_timeout_s=900.0, request_timeout_s=300.0,
                            fleet_dir=os.path.join(tmp, "fleet")).start()
        try:
            print(f"fleet_chip_check: worker booted and warmed in "
                  f"{time.perf_counter() - t0:.1f} s (info)", flush=True)
            url = f"http://127.0.0.1:{fleet.port}"
            prompt = [int(t) for t in
                      np.random.default_rng(0).integers(1, 30522, size=21)]
            req = urllib.request.Request(
                f"{url}/v1/models/{MODEL_ID}/generate",
                data=json.dumps({"prompt_tokens": [prompt],
                                 "max_new_tokens": 16}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                status, body = r.status, json.loads(r.read())
            tokens = body["tokens"][0]
            print(f"fleet_chip_check: generate -> {status}, "
                  f"{len(tokens)} tokens", flush=True)
            with urllib.request.urlopen(f"{url}/metrics", timeout=60) as r:
                metrics = r.read().decode()
            device_lines = [line for line in metrics.splitlines()
                            if line.startswith("dl4j_device_bytes_limit")]
            print("fleet_chip_check: worker devices: "
                  + "; ".join(device_lines), flush=True)
            ok = (status == 200 and len(tokens) == 16
                  and any('platform="tpu"' in line and 'worker="' in line
                          for line in device_lines))
        finally:
            fleet.stop()
            log = os.path.join(tmp, "fleet", "w0.log")
            if os.path.exists(log):
                with open(log, errors="replace") as f:
                    print("fleet_chip_check: worker log tail:\n"
                          + f.read()[-1500:], flush=True)
    print(json.dumps({"ok": ok, "front_platform": front}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
