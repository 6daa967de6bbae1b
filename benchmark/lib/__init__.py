"""The benchmark's yardstick: what later PRs may read and never change."""
