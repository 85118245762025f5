"""How a cell's traffic reaches the server: one module a kind, named by
the traffic mix's ``feed``. A mix that names none is sent as DogStatsD
datagrams, as every mix was before there was a choice."""

DEFAULT = "udp_statsd"
