"""The benchmark of the JaxTrainer path. Everything here is the yardstick:
later PRs add files and change none."""
