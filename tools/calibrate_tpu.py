"""Measure a HardwareSpec on the live TPU chip and persist it.

The autoparallel search (Galvatron-parity; reference
``tools/Galvatron/README.md:15-100`` profile→search→train workflow) consumes
a calibrated :class:`hetu_tpu.autoparallel.HardwareSpec`.  CPU CI calibrates
against the host; this script records the real-chip numbers as a committed
artifact (``artifacts/tpu_calibration.json``) so searches are grounded in
measured hardware.  A chip tool: run it on the machine with the chip.
"""
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    import jax

    from hetu_tpu.autoparallel import calibrate_hardware

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"refusing to calibrate the TPU on the {backend} backend",
              file=sys.stderr)
        return 1
    from artifact_schema import provenance

    spec = calibrate_hardware()
    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "spec": dataclasses.asdict(spec),
        **provenance({"kind": "hardware_calibration"}),
    }
    os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
    path = os.path.join(ROOT, "artifacts", "tpu_calibration.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
