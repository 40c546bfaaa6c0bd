"""Generator kinds, one a file: `plan(mix, seed, n, preload, serial_base)`
-> n traffic.Op, the same multiset of work for every seed."""
