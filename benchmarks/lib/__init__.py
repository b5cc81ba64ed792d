"""The yardstick: traffic generation, window arithmetic, trace reduction,
bytes-per-step and the peaks table. Later PRs may not change these."""
