"""Window drivers, one per kind of work; a traffic file names its
driver by module name."""
