"""NYM : ATTRIB at `nym_share`. Each NYM is trustee-signed and creates a
fresh DID; each ATTRIB is signed by its own preloaded DID, drawn without
replacement, so a wave holds many distinct signers. ATTRIB payloads cover
`attrib_raw_bytes` evenly: the same multiset of sizes for every seed."""
import random

from benchmarks.traffic import Op, attrib_raw


def plan(mix: dict, seed: int, n: int, preload: int,
         serial_base: int = 0) -> list[Op]:
    rng = random.Random(seed * 1_000_003 + 17)
    n_nym = int(round(n * mix["nym_share"]))
    n_attr = n - n_nym
    if n_attr > preload:
        raise SystemExit(f"traffic: {n_attr} ATTRIB signers wanted, "
                         f"{preload} DIDs preloaded")
    signers = rng.sample(range(preload), n_attr)
    lo, hi = mix["attrib_raw_bytes"]
    sizes = [lo + (hi - lo) * i // max(1, n_attr - 1) for i in range(n_attr)]
    rng.shuffle(sizes)
    kinds = ["NYM"] * n_nym + ["ATTRIB"] * n_attr
    rng.shuffle(kinds)
    ops, i_nym, i_attr = [], 0, 0
    for kind in kinds:
        if kind == "NYM":
            ops.append(Op("NYM", -1, serial_base + i_nym, ""))
            i_nym += 1
        else:
            s = signers[i_attr]
            ops.append(Op("ATTRIB", s, s, attrib_raw(
                seed, serial_base + i_attr, sizes[i_attr])))
            i_attr += 1
    return ops
