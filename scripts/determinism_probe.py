"""Determinism probe: verification results must be bit-stable across runs.

The reference's reproducible-build CI job builds the binary twice and
compares hashes (.github/workflows/main.yml:48-67).  The analogue for a
verification framework is result determinism: two fresh processes running
the same workload must produce byte-identical digests and masks (the
quorum is host integers over the mask).
Printed as canonical JSON; CI `cmp`s two runs.
"""

import json
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def main() -> None:
    from go_ibft_tpu.bench import build_round_workload
    from go_ibft_tpu.utils.jaxcache import enable_persistent_cache
    from go_ibft_tpu.verify.batch import _digest_kernel, _recover_kernel

    enable_persistent_cache()

    w = build_round_workload(8, corrupt_frac=0.25, seed=11)
    blocks, counts, r, s, v, senders, live = w.prepare
    zw = _digest_kernel(blocks, counts)
    mask = _recover_kernel(zw, r, s, v, senders, w.table, live)
    hz, sr, ss_, sv, signers, slive = w.seals
    smask = _recover_kernel(hz, sr, ss_, sv, signers, w.table, slive)
    json.dump(
        {
            "prepare_digests": np.asarray(zw).tolist(),
            "prepare_mask": np.asarray(mask).tolist(),
            "seal_mask": np.asarray(smask).tolist(),
        },
        sys.stdout,
        sort_keys=True,
    )
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
