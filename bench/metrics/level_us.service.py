"""``level_us.study``, read in the service cells."""

import registry

read = registry.module("metrics", "level_us.study").read
