"""``device_idle_pct.study``, read in the service cells."""

import registry

read = registry.module("metrics", "device_idle_pct.study").read
