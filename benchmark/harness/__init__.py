"""The benchmark's harness: the yardstick later PRs may not change."""
