"""Launch layer: meshes (``mesh``), the serving plan's sharding rules
(``sharding``), the compile-cache location (``compile_cache``), the
serving driver (``serve``) and the data plane's mesh dry-run
(``cache_dryrun``)."""
