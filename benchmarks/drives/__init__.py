"""Drive kinds, one a file: `stream_length(drive, seconds)` and
`schedule(drive, seed, seconds)` (see traffic.schedule)."""
