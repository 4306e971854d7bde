"""One driver per entry point of the program: `serving`, `equalizer`.
Each exposes `run(cell, seed, seconds, ctx) -> harness.Outcome`."""
