"""``execute_ms.study``, read in the service cells."""

import registry

read = registry.module("metrics", "execute_ms.study").read
