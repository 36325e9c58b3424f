"""The two ways a cell drives the program: ``engine`` (one chip, the
closed-loop wave scan with retries in place) and ``sharded`` (the routed
wave over a four-chip mesh).  A configuration names its driver."""
